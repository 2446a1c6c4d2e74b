"""``bench/trace.py``: the reduction from a profiler trace to busy time,
idle share, collective time, top operations and labelled idle gaps.

A synthetic trace with known overlaps checks the arithmetic exactly; a
small trace recorded here on the CPU checks that ``load`` finds the
operations and the harness's spans in what the profiler writes."""
import numpy as np
import pytest

from bench import trace as tr


def ops(*rows):
    """Ops from (name, start, end) rows, as the CPU trace names them."""
    return tr.Ops(np.array([r[1] for r in rows], np.int64),
                  np.array([r[2] for r in rows], np.int64),
                  [r[0] for r in rows], [tr.op_name(r[0])[1] for r in rows])


@pytest.mark.parametrize("rows,busy", [
    ([], 0),
    ([("a", 0, 10)], 10),
    ([("a", 0, 10), ("b", 5, 15)], 15),            # overlap
    ([("a", 0, 10), ("b", 2, 3)], 10),             # nested
    ([("a", 0, 10), ("b", 10, 20)], 20),           # touching
    ([("b", 30, 40), ("a", 0, 10), ("c", 5, 12)], 22),   # unsorted
    ([("a", -5, 5), ("b", 95, 130)], 10),          # clipped to [0, 100)
])
def test_busy_is_the_union_clipped_to_the_window(rows, busy):
    assert tr.busy_ns(ops(*rows) if rows else ops(), 0, 100) == busy


def synthetic():
    """Window [100, 200) on two devices.  Device 0: compute 100-130 and
    140-150 with an all-to-all 120-135 overlapping it, and a last op
    180-190; device 1: busy 100-200.  Host spans: the window, one unit
    over 100-170 holding an engine call 100-160, a to_original 160-170,
    and a second unit from 175 (its engine call from 176)."""
    dev0 = ops(("fusion.1", 100, 130), ("all-to-all.3", 120, 135),
               ("fusion.1", 140, 150), ("sort.2", 180, 190),
               ("fusion.9", 20, 40))      # before the window
    dev1 = ops(("fusion.1", 100, 200))
    spans = [("window", 100, 200), ("unit", 100, 170),
             ("engine_call", 100, 160), ("to_original", 160, 170),
             ("unit", 175, 200), ("engine_call", 176, 200)]
    return tr.Trace({0: dev0, 1: dev1}, spans)


def test_reduce_synthetic_trace_exactly():
    r = tr.reduce(synthetic())
    # device 0 busy: [100,135) + [140,150) + [180,190) = 35 + 10 + 10
    assert r["busy_s_by_device"] == {0: 55e-9, 1: 100e-9}
    assert r["busy_s"] == pytest.approx(77.5e-9, abs=1e-18)
    assert r["window_s"] == pytest.approx(100e-9, abs=1e-18)
    assert r["idle_share"] == pytest.approx(1 - 77.5 / 100)
    assert r["first_busy_s"] == pytest.approx(55e-9, abs=1e-18)
    assert r["collective_s"] == pytest.approx(15e-9, abs=1e-18)
    assert r["device_ops"] == [["fusion.1", 40e-9], ["all-to-all.3", 15e-9],
                               ["sort.2", 10e-9]]
    # gaps on device 0: [135,140) in the engine call, [150,180) whose
    # middle (165) is in to_original, [190,200) in the second call
    assert r["idle_gaps"] == [["to_original", 30e-9], ["engine_call", 10e-9],
                              ["engine_call", 5e-9]]


@pytest.mark.parametrize("t,want", [(0, 0), (5, 0), (12, 2), (20, 10),
                                    (25, 10), (35, 15), (100, 20)])
def test_covered_time_before_an_instant(t, want):
    # busy [10, 20) and [30, 40)
    s, e = np.array([10, 30]), np.array([20, 40])
    assert tr.covered_ns(s, e, np.array([t]))[0] == want


def test_collectives_in_flight_count_only_while_busy():
    """An all-reduce started asynchronously at 120 and done at 170 counts
    for [120, 135) and [140, 150), when device 0 is busy; the idle
    [150, 170) is not collective time."""
    t = synthetic()
    t.devices[0] = ops(("fusion.1", 100, 135), ("fusion.2", 140, 150))
    t.in_flight[0] = ops(("all-reduce-start.7", 120, 170),
                         ("copy-start.2", 100, 200))
    assert tr.reduce(t)["collective_s"] == pytest.approx(25e-9, abs=1e-18)


def test_self_time_nets_out_nested_operations():
    # a while loop 0-100 whose body ran 10-20 and 30-50 (one op inside
    # another at 35-40), and an async copy 90-120 overlapping its end
    o = ops(("while.1", 0, 100), ("fusion.2", 10, 20), ("sort.3", 30, 50),
            ("fusion.4", 35, 40), ("copy-start.5", 90, 120))
    assert tr.self_ns(o.start, o.end).tolist() == [70, 10, 15, 5, 30]


@pytest.mark.parametrize("text,want", [
    ("%while.25 = (f32[16,8192]{1,0:T(8,128)S(1)}, pred[16]{0}) "
     "while((f32[16]) %tuple.438), condition=%c", ("while.25", "while")),
    ("%all-to-all.3 = s32[4,16]{1,0:T(4,128)} all-to-all(s32[4,16]{1,0} %x)",
     ("all-to-all.3", "all-to-all")),
    ("%fusion.442 = s32[256]{0:T(256)S(1)} fusion(s32[8]{0} %b), kind=kCustom",
     ("fusion.442", "fusion")),
    ("pmax.44", ("pmax.44", "pmax")),
])
def test_op_names_and_opcodes(text, want):
    assert tr.op_name(text) == want


@pytest.mark.parametrize("t,want", [(105, "engine_call"), (165, "to_original"),
                                    (172, "window"), (250, "none")])
def test_gap_label_is_the_innermost_span(t, want):
    assert tr.label(synthetic().spans, t) == want


def test_reduce_needs_a_window_span():
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace(synthetic().devices, []))


def test_load_reads_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: jax.lax.fori_loop(
        0, 20, lambda i, a: jnp.sin(a) @ a * 1e-3, x))
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("window"):
        for _ in range(3):
            with TraceAnnotation("unit"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path), ("window", "unit"))
    assert sorted(n for n, _, _ in t.spans) == ["unit"] * 3 + ["window"]
    assert t.devices and all(o.start.size for o in t.devices.values())
    r = tr.reduce(t)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 <= r["idle_share"] < 1
    assert r["device_ops"] and all(dt > 0 for _, dt in r["device_ops"])
    assert {lab for lab, _ in r["idle_gaps"]} <= {"window", "unit"}
