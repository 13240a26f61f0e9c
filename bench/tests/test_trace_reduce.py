"""The trace reduction: pure interval arithmetic on a made-up profile,
and the reader on a small trace recorded on a TPU v5e (two jitted
programs, run three times with 2 ms host sleeps between them, after the
harness's clock marker)."""

import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "tpu_v5e_small.xplane.pb")


def test_merge_clip_gaps():
    busy = tr.merge([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.clip(busy, 1.0, 3.5) == [(1.0, 2.0), (3.0, 3.5)]
    assert tr.gaps(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                        (4.0, 5.0)]


def _op(name, mod, a, b):
    return tr.Op(name, mod, a, b)


def test_reduce_profile_by_hand():
    ops = [_op("%fusion.1 fusion", "jit_decode_step(1)", 0.0, 1.0),
           _op("%_run.2 custom-call", "jit_decode_step(1)", 1.0, 3.0),
           _op("%fusion.3 fusion", "jit_prefill_step(2)", 5.0, 6.0),
           _op("%_run.4 custom-call", "jit_prefill_step(2)", 6.0, 6.5)]
    prof = tr.Profile({"/device:TPU:0": ops}, marker=0.0)
    r = tr.reduce_profile(prof, lo=0.5, hi=8.0, host_spans=[
        (3.0, 4.0, "serve_admit"), (6.5, 8.0, "serve_decode_step")])
    # busy: [0.5, 3] + [5, 6.5] = 4.0 of the 7.5 s window
    assert r["busy_s"] == pytest.approx(4.0)
    assert r["kernel_s"] == {"decode_step": pytest.approx(2.0),
                             "prefill_step": pytest.approx(0.5)}
    idle = dict(map(tuple, r["breakdown"]["idle_gaps"]))
    # gap [3, 5]: its midpoint 4.0 lies in the admit span; [6.5, 8] in
    # the decode span
    assert idle == {"serve_admit": pytest.approx(2.0),
                    "serve_decode_step": pytest.approx(1.5)}
    top = r["breakdown"]["device_ops"][0]
    assert top == ["decode_step/%_run.2 custom-call", pytest.approx(2.0)]


def test_op_names_from_hlo_text():
    assert tr.op_name("%_run.9 = bf16[10,8]{1,0} custom-call(s32[3] "
                      "%iota.1), custom_call_target=\"tpu\"") == \
        ("%_run.9 custom-call", "custom-call")
    assert tr.op_name("%while.5 = (s32[], bf16[2]{0}) while((s32[], "
                      "bf16[2]{0}) %t), condition=%c") == \
        ("%while.5 while", "while")


def test_reduce_profile_averages_over_chips():
    a = [_op("fusion.1", "m", 0.0, 1.0)]
    b = [_op("fusion.1", "m", 0.0, 3.0)]
    prof = tr.Profile({"/device:TPU:0": a, "/device:TPU:1": b}, 0.0)
    r = tr.reduce_profile(prof, lo=0.0, hi=4.0, host_spans=[])
    assert r["busy_s"] == pytest.approx(2.0)


def test_recorded_tpu_trace():
    prof = tr.load(RECORDED)
    assert list(prof.ops) == ["/device:TPU:0"]
    ops = prof.ops["/device:TPU:0"]
    assert prof.marker is not None
    mods = {o.module for o in ops}
    assert len(mods) >= 2 and "" not in mods
    lo, hi = prof.marker, max(o.end for o in ops)
    r = tr.reduce_profile(prof, lo=lo, hi=hi, host_spans=[])
    assert 0.0 < r["busy_s"] < hi - lo
    idle = dict(map(tuple, r["breakdown"]["idle_gaps"]))
    # the three 2 ms sleeps show as idle time
    assert sum(idle.values()) >= 3 * 0.002
    assert not r["kernel_s"]            # no Pallas kernel in these programs
