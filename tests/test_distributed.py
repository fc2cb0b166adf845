"""Multi-device integration tests (subprocess with 8 fake CPU devices):
MoE dispatch equivalence, compressed/hierarchical collectives, GPipe
pipeline parallelism, sharded train step."""
import pytest

from helpers import run_with_devices

pytestmark = pytest.mark.slow


def test_moe_sharded_matches_local():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch
from repro.dist.context import make_dist
from repro.launch.mesh import make_test_mesh
from repro.models.moe import moe_block, moe_init, expert_layout
import dataclasses

cfg = get_arch('deepseek-v3-671b').reduced()
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, n_experts=8, top_k=2, capacity_factor=8.0))
mesh = make_test_mesh((2, 4), ('data', 'model'))
dist = make_dist(mesh)

key = jax.random.key(0)
p_local = moe_init(key, cfg, jnp.float32, 1)      # [d, 8*ff]
p_shard = moe_init(key, cfg, jnp.float32, 4)      # [d, 4*2*ff]
# same logical experts: slot m*2+j is device m's j-th expert
p_shard = dict(p_shard)
for k in ('up', 'down', 'gate'):
    p_shard[k] = p_local[k].reshape(p_shard[k].shape)
p_shard['router'] = p_local['router']
p_shard['shared'] = p_local['shared']

x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model)) * 0.5
from repro.dist.context import no_dist
y_ref, aux_ref = moe_block(p_local, x, cfg, no_dist())
with mesh:
    for dispatch in ('a2a', 'replicated'):
        y, aux = jax.jit(lambda p, x: moe_block(p, x, cfg, dist, dispatch=dispatch))(p_shard, x)
        err = float(jnp.abs(y - y_ref).max())
        scale = float(jnp.abs(y_ref).max())
        assert err < 5e-4 * max(scale, 1), (dispatch, err, scale)
        print(dispatch, 'ok', err)
print('PASS')
""")
    assert "PASS" in out


def test_compressed_allreduce_and_error_feedback():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.dist.collectives import compressed_allreduce

mesh = make_test_mesh((8,), ('data',))
g_global = jax.random.normal(jax.random.key(0), (8, 256)) * 0.1

def body(g, e):
    m, e2 = compressed_allreduce(g[0], e[0], 'data')
    return m[None], e2[None]

with mesh:
    f = jax.shard_map(body, mesh=mesh, in_specs=(P('data', None), P('data', None)),
                      out_specs=(P('data', None), P('data', None)), check_vma=False)
    err0 = jnp.zeros_like(g_global)
    mean, err = f(g_global, err0)
    true_mean = g_global.mean(0)
    # every shard holds (approximately) the true mean
    for i in range(8):
        rel = float(jnp.abs(mean[i] - true_mean).max() / (jnp.abs(true_mean).max() + 1e-9))
        assert rel < 0.05, rel
    # error feedback: residual equals what quantization dropped
    assert float(jnp.abs(err).max()) < float(jnp.abs(g_global).max()) * 0.02
print('PASS')
""")
    assert "PASS" in out


def test_hierarchical_allreduce_multipod():
    out = run_with_devices("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.dist.collectives import hierarchical_allreduce

mesh = make_test_mesh((2, 4), ('pod', 'data'))
x = jax.random.normal(jax.random.key(0), (8, 64))

def body(xl):
    return hierarchical_allreduce(xl, 'pod', 'data', scatter_dim=0)[None]

with mesh:
    f = jax.shard_map(lambda xl: body(xl[0]), mesh=mesh,
                      in_specs=P(('pod', 'data'), None),
                      out_specs=P(('pod', 'data'), None), check_vma=False)
    out = f(x)
    want = x.sum(0)
    for i in range(8):
        assert float(jnp.abs(out[i] - want).max()) < 1e-4
print('PASS')
""")
    assert "PASS" in out


def test_gpipe_matches_sequential():
    out = run_with_devices("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_test_mesh
from repro.dist.pipeline import gpipe_apply

mesh = make_test_mesh((4,), ('stage',))
L, d = 8, 16
ws = jax.random.normal(jax.random.key(0), (L, d, d)) * (1.0 / d ** 0.5)

def layer(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(jax.random.key(1), (6, 2, 4, d))  # [n_micro, mb, S, d]

# sequential reference
ref = x
for i in range(L):
    ref = layer(ws[i], ref)

with mesh:
    got = gpipe_apply(layer, ws, x, mesh=mesh, layers_per_stage=L // 4)
err = float(jnp.abs(got - ref).max())
assert err < 1e-5, err
print('PASS', err)
""")
    assert "PASS" in out


def test_sharded_train_step_runs():
    out = run_with_devices("""
import jax, jax.numpy as jnp
from repro.configs import get_arch
from repro.dist.context import make_dist
from repro.launch.mesh import make_test_mesh
from repro.models.api import build_model
from repro.train.loop import jit_train_step, init_train_state
from repro.train.optimizer import OptConfig
from jax.sharding import PartitionSpec as P

cfg = get_arch('qwen1.5-0.5b').reduced()
mesh = make_test_mesh((2, 4), ('data', 'model'))
dist = make_dist(mesh)
model = build_model(cfg, dist)
opt = OptConfig(lr=1e-3)
with mesh:
    state = init_train_state(model, jax.random.key(0), opt)
    in_specs = {'tokens': P('data', None), 'targets': P('data', None)}
    step = jit_train_step(model, opt, grad_accum=2, batch_specs=in_specs)
    batch = {'tokens': jax.random.randint(jax.random.key(1), (8, 64), 0, cfg.vocab),
             'targets': jax.random.randint(jax.random.key(2), (8, 64), 0, cfg.vocab)}
    l0 = None
    for i in range(4):
        state, m = step(state, batch)
        if l0 is None: l0 = float(m['loss'])
    l1 = float(m['loss'])
assert l1 < l0, (l0, l1)   # overfits one repeated batch
print('PASS', l0, '->', l1)
""")
    assert "PASS" in out
