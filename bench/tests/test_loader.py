"""The harness finds every file a cell names; names follow the
benchmark's rules; the traffic generator follows its file and the seed;
and a run without a TPU fails with no result."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import generator
import harness
from conftest import ROOT

BENCH = harness.load_benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_every_file(cell):
    c = harness.load_cell(ROOT, BENCH, cell)
    assert c.metrics(False) and c.metrics(True)
    for m in c.metrics(False) + c.metrics(True):
        assert callable(harness.metric(m["name"]).read)
    cfg = c.program_config()                # the program's sizes, checked
    assert cfg.n_layers == c.spec.n_layers and cfg.attn_impl == "ff"
    names = {m["name"] for m in c.metrics(False)}
    assert "setup_s" in names and len(names) >= 2


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
            for text in (e.get("why"), e.get("layer"), e.get("source")):
                if text is not None:
                    assert 1 <= len(text) <= 200 and "\n" not in text
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in BENCH["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cuts(conf):
    with open(os.path.join(ROOT, conf["file"])) as f:
        c = json.load(f)
    assert c["source"].startswith(conf["source"])
    assert c["reduced"] == conf["reduced"]
    for k in c["reduced"]:
        assert k in c["assumed"]
        # never a width: hidden, intermediate, head or projection sizes
        assert not (k.endswith(("_dim", "_rank")) or k in (
            "hidden_size", "intermediate_size", "head_dim"))
    assert c["deployment"]


@pytest.mark.parametrize("mix", sorted({w["traffic"]
                                         for w in BENCH["workloads"]}))
def test_generator_follows_seed_and_file(mix):
    with open(os.path.join(harness.BENCH_DIR, "traffic",
                           mix + ".json")) as f:
        t = json.load(f)
    a = generator.make_requests(t, seed=2**33 + 1, seconds=30, vocab=1000)
    b = generator.make_requests(t, seed=2**33 + 1, seconds=30, vocab=1000)
    c = generator.make_requests(t, seed=5, seconds=30, vocab=1000)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new ==
               y.max_new for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))
    # every seed sends the same lengths in the same order (the schedule);
    # only the tokens differ
    k = t["clients"]
    lens = lambda rs: [(len(r.prompt), r.max_new) for r in rs]
    assert lens(a) == lens(c)
    assert len(a) == k + max(k, int(np.ceil(
        t["requests_per_window_second"] * 30)))
    for key, got in (("prompt_len", [len(r.prompt) for r in a]),
                     ("output_len", [r.max_new for r in a])):
        d = t[key]
        assert d["min"] <= min(got) and max(got) <= d["max"]
        assert abs(np.median(got) - d["median"]) <= 0.1 * d["median"]
        logs = np.log(np.clip(got, d["min"] + 1, d["max"] - 1))
        q1, q3 = np.percentile(logs, [25, 75])
        # the lognormal's quartiles are median * exp(-+0.674 sigma)
        assert abs((q3 - q1) / 1.349 - d["sigma"]) <= 0.3 * d["sigma"]


def test_run_without_tpu_fails_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr
