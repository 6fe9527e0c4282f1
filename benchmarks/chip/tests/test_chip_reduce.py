"""The trace reduction on a recorded excerpt of a TPU v5e trace (60 ms of
the dsr1d-reasoning-backlog cell around a decode-chunk boundary, op names
cut to 160 characters), and the loader on a trace recorded here."""
import glob

import pytest

from tiny import HERE
import reduce

KERNEL = r"^%paged_gqa_decode\.\d+ = "


@pytest.fixture(scope="module")
def excerpt():
    t = reduce.Trace.from_json((HERE / "data/trace_excerpt.json").read_text())
    return t, *reduce.window(t)


def test_window_is_the_annotation(excerpt):
    t, lo, hi = excerpt
    assert (hi - lo) == 60_000_000


def test_busy_is_the_union_of_leaf_ops(excerpt):
    t, lo, hi = excerpt
    busy = reduce.busy_s(t.ops, lo, hi)
    # a chunk boundary lies inside: the device idles part of the 60 ms
    assert 0.04 < busy < 0.06
    # loop ops span their bodies, gaps included, and do not count
    loops = [e for e in t.ops if e[0].startswith("%while")]
    assert loops and reduce.busy_s(loops, lo, hi) == 0.0
    # the union never exceeds the sum of the intervals
    assert busy <= sum(b - a for _, a, b in reduce._clip(
        reduce.leaves(t.ops), lo, hi)) / 1e9


def test_kernel_time_and_programs(excerpt):
    t, lo, hi = excerpt
    k = reduce.time_s(t.ops, KERNEL, lo, hi)
    chunk = reduce.programs_s(t, r"^jit_", lo, hi, holding=KERNEL)
    small = reduce.programs_s(t, r"^jit_convert", lo, hi, lacking=KERNEL)
    assert 0 < k < chunk <= 0.06
    # the small programs between chunks run no kernel
    assert 0 < small < 1e-4
    assert reduce.programs_s(t, r"^jit_convert", lo, hi, holding=KERNEL) == 0


def test_top_names_drop_instruction_numbers(excerpt):
    t, lo, hi = excerpt
    top = reduce.top(t.ops, lo, hi, 3)
    assert [n for n, _ in top][:2] == ["copy", "paged_gqa_decode"]
    assert top[0][1] >= top[1][1] >= top[2][1]
    assert reduce.op_name("%copy.48 = bf16[2] copy(x)") == "copy"
    assert reduce.op_name("%dynamic-slice_bitcast_fusion.4.remat = f(x)") \
        == "dynamic-slice_bitcast_fusion"


def test_idle_gaps_are_named_by_the_host(excerpt):
    t, lo, hi = excerpt
    gaps = reduce.idle_gaps(t, lo, hi, 3)
    assert len(gaps) == 3
    assert gaps[0][1] >= gaps[1][1] >= gaps[2][1] > 0
    # the longest gap: the host is reading the chunk's tokens back
    assert gaps[0][0] == "np.asarray(jax.Array)"


def test_union_merges_overlaps():
    evs = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 32, 1)]
    assert reduce.union(evs, 0, 100) == [(0, 15), (30, 35)]
    assert reduce.union(evs, 8, 31) == [(8, 15), (30, 31)]


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(reduce.WINDOW):
            with jax.profiler.TraceAnnotation("bench.run"):
                f(x).block_until_ready()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    t = reduce.load(path)
    lo, hi = reduce.window(t)
    assert hi > lo
    assert "bench.run" in {n for n, _, _ in t.host}
