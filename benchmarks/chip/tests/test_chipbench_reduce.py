"""The trace reduction: interval arithmetic on a hand-made record, and the
extraction and reduction of a recorded TPU v5e trace (five runs of one jitted
two-matmul program, host spans around dispatch and wait)."""
from pathlib import Path

import pytest

import chipbench_tiny  # noqa: F401
from chipbench import reduce, tracing

DATA = Path(__file__).resolve().parent / "data"


def test_union_subtract_measure():
    u = reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert reduce.measure(u) == 6
    assert reduce.subtract(u, [(2, 6)]) == [(0, 2), (6, 8)]
    assert reduce.subtract([(0, 10)], [(1, 2), (4, 5)]) == [(0, 1), (2, 4), (5, 10)]
    assert reduce.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def _handmade():
    # one device, window [0, 100) ns: compute 0-40, all-reduce 30-60 (10 of it
    # under compute), compute 70-80, an async all-gather span 75-95
    ops = [[0, 40, "fusion.1|fusion"], [30, 30, "all-reduce.2|all-reduce"],
           [70, 10, "fusion.3|fusion"]]
    asyn = [[75, 20, "all-gather-start|all-gather-start"]]
    mods = [[0, 60, "jit_decode(1)"], [70, 10, "jit_decode(1)"]]
    return {"window": [0, 100], "host": [[0, 100, "bench.window"],
                                          [60, 10, "bench.decode_call"]],
            "devices": {"0": {"ops": ops, "async": asyn, "modules": mods}}}


def test_handmade_record():
    rec = _handmade()
    assert reduce.busy(rec) == {"0": pytest.approx(70e-9)}
    assert reduce.idle_share(rec) == pytest.approx(0.30)
    c = reduce.comm(rec)
    # collective union: 30-60 and 75-95 = 50 ns; exposed: 40-60 and 80-95 = 35
    assert c["collective_s"] == pytest.approx(50e-9)
    assert c["exposed_s"] == pytest.approx(35e-9)
    assert reduce.top_ops(rec)[0] == ["fusion.1", pytest.approx(40e-9)]
    gaps = dict((n, v) for n, v in reduce.idle_gaps(rec))
    assert gaps == {"bench.decode_call": pytest.approx(10e-9),
                    "host": pytest.approx(20e-9)}
    assert reduce.module_gaps(rec, "decode") == pytest.approx(10e-9)
    assert reduce.module_gaps(rec, "prefill") is None


def test_no_device_planes_read_nothing():
    rec = {"window": [0, 1], "host": [], "devices": {}}
    assert reduce.idle_share(rec) is None and reduce.comm(rec) is None


def test_recorded_tpu_trace():
    rec = tracing.extract(str(DATA / "matmul_loop.xplane.pb"))
    assert set(rec["devices"]) == {"0"}
    d = rec["devices"]["0"]
    assert len(d["modules"]) == 5 and len(d["ops"]) == 20
    assert all(name.startswith("jit__lambda") for _, _, name in d["modules"])
    # each run: an async weight prefetch, then two matmul fusions of ~91 us
    fus = [dur for _, dur, name in d["ops"] if name.endswith("|fusion")]
    assert len(fus) == 10 and all(90e3 < x < 92e3 for x in fus)
    names = {h[2] for h in rec["host"]}
    assert names == {"bench.dispatch", "bench.wait"} and rec["window"] is None
    # without a window span the window is the span of the device's ops
    lo, hi = reduce.window_ns(rec)
    first = min(s for s, _, _ in d["ops"])
    assert lo == first and hi == max(s + x for s, x, _ in d["ops"])
    m = sorted(d["modules"])
    rec["window"] = [m[0][0], m[-1][0] + m[-1][1]]
    lo, hi = reduce.window_ns(rec)
    busy = reduce.busy(rec)["0"]
    assert 5 * 181e-6 < busy < 5 * 183e-6
    assert reduce.idle_share(rec) == pytest.approx(1 - busy / ((hi - lo) * 1e-9))
    gap = reduce.module_gaps(rec, "jit__lambda")
    want = sum(b[0] - (a[0] + a[1]) for a, b in zip(m, m[1:])) / 4 * 1e-9
    assert gap == pytest.approx(want) and gap > 10e-3   # a 10 ms sleep per run
    assert reduce.comm(rec)["collective_s"] == 0.0
    top = reduce.top_ops(rec)
    assert top[0][0] in ("convolution_tanh_fusion", "fusion")


def _brute(rec, step_ns):
    """Busy, collective and exposed seconds per device by marking time bins
    (an independent count of what the interval arithmetic computes)."""
    import numpy as np

    lo, hi = reduce.window_ns(rec)
    n = int((hi - lo) // step_ns) + 1
    out = {}
    for dev, d in rec["devices"].items():
        busy, coll, other = (np.zeros(n, bool) for _ in range(3))
        for s, dur, name in d["ops"] + d["async"]:
            if reduce.is_container(name):
                continue
            a = int(max(s - lo, 0) // step_ns)
            b = int(min(s + dur - lo, hi - lo) // step_ns)
            if b <= a:
                continue
            if reduce.is_collective(name):
                coll[a:b] = True
            if (s, dur, name) in map(tuple, d["ops"]):
                busy[a:b] = True
                if not reduce.is_collective(name):
                    other[a:b] = True
        out[dev] = (busy.sum() * step_ns * 1e-9, coll.sum() * step_ns * 1e-9,
                    (coll & ~other).sum() * step_ns * 1e-9)
    return out


@pytest.mark.parametrize("rec", [_handmade(), "matmul_loop.xplane.pb"])
def test_interval_arithmetic_matches_a_bin_count(rec):
    if isinstance(rec, str):
        rec = tracing.extract(str(DATA / rec))
    step = 1 if len(rec["devices"]["0"]["ops"]) < 10 else 1000
    brute = _brute(rec, step)
    busy = reduce.busy(rec)
    c = reduce.comm(rec)
    tol = 2e-9 * step * 50
    for dev, (b, coll, exp) in brute.items():
        assert busy[dev] == pytest.approx(b, abs=tol)
    n = len(brute)
    assert c["collective_s"] == pytest.approx(sum(v[1] for v in brute.values()) / n, abs=tol)
    assert c["exposed_s"] == pytest.approx(sum(v[2] for v in brute.values()) / n, abs=tol)
