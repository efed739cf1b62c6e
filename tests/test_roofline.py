"""Loop-aware HLO analysis: validated against analytic FLOP counts and XLA's
own cost model on loop-free programs; collective parsing under 8 devices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro.launch import hlo_analysis as H


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_matmul_flops_exact():
    a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    comp = _compile(lambda a, b: a @ b, a, b)
    t = H.analyze_text(comp.as_text())
    assert t.dot_flops == 2 * 256 * 512 * 128
    ca = comp.cost_analysis()
    if isinstance(ca, list):                 # older jax returns [dict]
        ca = ca[0]
    assert t.dot_flops == float(ca["flops"])


def test_scan_flops_multiplied():
    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(y)
    ws = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    t = H.analyze_text(_compile(f, ws, x).as_text())
    assert t.dot_flops == 7 * 2 * 8 * 64 * 64
    assert not t.warnings


def test_nested_scan_flops():
    def f(ws, x):
        def outer(x, w):
            def inner(x, _):
                return jnp.tanh(x @ w), None
            x, _ = jax.lax.scan(inner, x, None, length=3)
            return x, None
        y, _ = jax.lax.scan(outer, x, ws)
        return y.sum()
    ws = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    t = H.analyze_text(_compile(f, ws, x).as_text())
    assert t.dot_flops == 7 * 3 * 2 * 8 * 64 * 64


def test_batched_dot_flops():
    a = jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 64, 16), jnp.float32)
    comp = _compile(lambda a, b: jnp.einsum("bij,bjk->bik", a, b), a, b)
    t = H.analyze_text(comp.as_text())
    assert t.dot_flops == 2 * 4 * 32 * 64 * 16


def test_dynamic_while_flagged():
    def f(x):
        def cond(s):
            return jnp.sum(s) < 100.0
        def body(s):
            return s @ s
        return jax.lax.while_loop(cond, body, x)
    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    t = H.analyze_text(_compile(f, x).as_text())
    assert t.warnings, "dynamic while should be flagged"


def test_bytes_scale_with_tensor_size():
    a1 = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    a2 = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    f = lambda a: jnp.tanh(a) * 2 + 1
    t1 = H.analyze_text(_compile(f, a1).as_text())
    t2 = H.analyze_text(_compile(f, a2).as_text())
    assert 10 <= t2.bytes / t1.bytes <= 22          # ~16x data, fusion noise


def test_collective_bytes_parsed():
    run_subprocess("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import hlo_analysis as H
mesh = jax.make_mesh((8,), ("d",))

def f(x):
    return jax.shard_map(lambda a: jax.lax.psum(a, "d"), mesh=mesh,
                         in_specs=P("d"), out_specs=P())(x)
x = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
comp = jax.jit(f).lower(x).compile()
t = H.analyze_text(comp.as_text())
assert "all-reduce" in t.coll_by_op, t.coll_by_op
# per-device tensor is (1, 1024) f32 = 4096 B; all-reduce counts 2x
assert t.coll_by_op["all-reduce"] == 2 * 4096, t.coll_by_op
print("OK")
""")


def test_roofline_terms_and_bottleneck():
    from repro.launch import roofline as rl
    a = jax.ShapeDtypeStruct((2048, 2048), jnp.float32)
    comp = _compile(lambda a: a @ a, a)
    r = rl.analyze(comp, n_chips=1, model_flops=2 * 2048 ** 3)
    assert r.bottleneck in ("compute", "memory")
    assert abs(r.useful_ratio - 1.0) < 0.1
    assert r.compute_s == r.flops / rl.PEAK_FLOPS


def test_model_flops_for_shapes():
    from repro.launch.roofline import model_flops_for
    from repro.configs import get_config, active_params
    cfg = get_config("h2o-danube-1.8b")
    n = active_params(cfg)
    assert model_flops_for(cfg, "train_4k") == 6.0 * n * 4096 * 256
    assert model_flops_for(cfg, "decode_32k") == 2.0 * n * 128
