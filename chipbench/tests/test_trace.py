"""The trace reduction, on synthetic events and on a trace recorded here on
the CPU (a jitted program and a Pallas call in interpret mode)."""
import time

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from chipbench import trace as tr

TPU_OP = ('%mcmc_sweep.1 = (f32[8,2000]{1,0}) custom-call(f32[2000,2000]{1,0} '
          '%store_0_.1), custom_call_target="tpu_custom_call"')


def test_op_name_drops_percent_and_suffix():
    assert tr.op_name(TPU_OP) == "mcmc_sweep"
    assert tr.op_name("%convert_multiply_fusion = f32[256,8,4] fusion(x)") == \
        "convert_multiply_fusion"
    assert tr.op_name("%fusion.17 = f32[8] fusion(x)") == "fusion"
    assert tr.op_name("dot_general.1") == "dot_general"
    assert tr.op_name("copy-done") == "copy-done"


def synthetic():
    ops = [[("a", 10, 20, False), ("k", 15, 30, True), ("a", 50, 60, False),
            ("k", 70, 100, True)]]
    spans = [("chipbench.window", 0, 100), ("chipbench.run_resilient", 30, 48),
             ("chipbench.block_wait", 60, 70), ("chipbench.inner", 32, 40)]
    return tr.Trace(ops=ops, spans=spans, window=(0, 100))


def test_busy_union_and_idle():
    t = synthetic()
    assert tr.busy_intervals(t.ops[0]) == [(10, 30), (50, 60), (70, 100)]
    assert tr.busy_s(t) == pytest.approx(60e-9)
    assert tr.idle_share(t) == pytest.approx(0.4)
    assert tr.idle_gaps(t) == [(0, 10), (30, 50), (60, 70)]


def test_kernel_and_non_kernel_time():
    t = synthetic()
    assert tr.op_seconds(t, lambda n, k: k) == pytest.approx(45e-9)
    assert tr.op_seconds(t, lambda n, k: not k) == pytest.approx(20e-9)
    assert tr.top_ops(t) == [["k", pytest.approx(45e-9)],
                             ["a", pytest.approx(20e-9)]]


def test_idle_gaps_go_to_the_innermost_host_span():
    t = synthetic()
    assert tr.host_activity(t.spans, 35) == "inner"
    assert tr.host_activity(t.spans, 45) == "run_resilient"
    assert tr.host_activity(t.spans, 200) == "untraced"
    got = dict(tr.idle_by_activity(t))
    assert got == {"window": pytest.approx(10e-9),
                   "run_resilient": pytest.approx(20e-9),
                   "block_wait": pytest.approx(10e-9)}


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2


def test_reduction_of_a_recorded_cpu_trace(tmp_path):
    kernel = jax.jit(lambda x: pl.pallas_call(
        _double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True)(x))
    prog = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128), jnp.float32)
    kernel(prog(x)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("chipbench.run_resilient"):
                    y = kernel(prog(x))
                with jax.profiler.TraceAnnotation("chipbench.block_wait"):
                    y.block_until_ready()
                with jax.profiler.TraceAnnotation("chipbench.host_work"):
                    time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)),
                device_plane=lambda n: n == "/host:CPU",
                op_line=lambda n: n.startswith("tf_XLA"))
    assert 0.15 <= t.window_s < 30
    busy = tr.busy_s(t)
    assert 0 < busy < t.window_s
    assert 0 < tr.idle_share(t) < 1
    assert tr.op_seconds(t, lambda n, k: n == "dot_general") > 0
    for dev in t.ops:       # clipped to the window
        assert all(t.window[0] <= a <= b <= t.window[1] for _, a, b, _ in dev)
    idle = tr.idle_by_activity(t)
    assert idle[0][0] == "host_work" and idle[0][1] >= 0.14
    assert sum(v for _, v in idle) == pytest.approx(t.window_s - busy)
    names = {n for n, _ in tr.top_ops(t, top=100)}
    assert "dot_general" in names


def test_a_trace_without_its_window_span_is_refused(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(3).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="window"):
        tr.load(tr.find_xplane(str(tmp_path)))
