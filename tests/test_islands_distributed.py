"""Distributed island evolution + sharded MoE + dry-run mini-mesh tests.

These run in subprocesses with fake host devices (see conftest) so the main
test process keeps its single-device view.
"""
from tests.conftest import run_multidevice


def test_island_evolution_and_psum_fitness_exactness():
    out = run_multidevice(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import gates
from repro.core.genome import CircuitSpec, init_genome, Genome, opcodes
from repro.core import encoding as E
from repro.core.evolve import EvolveConfig, make_eval_fn
from repro.core.islands import IslandConfig, evolve_islands, best_island, pad_words_for, _make_psum_eval_fn
from repro.launch.mesh import make_host_mesh
from functools import partial

mesh = make_host_mesh(data=2, model=4)
rng = np.random.RandomState(0)
R = 2000
X = rng.randn(R, 5)
y = ((X[:,0] > 0) | (X[:,2] > 1.0)).astype(np.int64)
enc = E.fit_encoder(X, E.EncodingConfig("quantile", 2))
bits = E.encode(enc, X)
data = E.pack_dataset(bits, y, 2, pad_words_to=pad_words_for(mesh, ("data",)))
W = data.x_words.shape[1]
mtr, mva = E.split_masks(R, W, 0.5, seed=1)
spec = CircuitSpec(bits.shape[1], 50, 1, gates.FULL_FS)

# exactness: psum-sharded fitness == single-device fitness
g = jax.vmap(lambda k: init_genome(k, spec))(jax.random.split(jax.random.key(5), 3))
ft_ref, fv_ref = make_eval_fn(spec, data, mtr, mva)(g)
@partial(jax.shard_map, mesh=mesh,
         in_specs=(P(), P(None,"data"), P(None,"data"), P(None,"data"),
                   P("data"), P("data"), P("data")),
         out_specs=P(), check_vma=False)
def f(gt, xw, yw, cw, mw, mt, mv):
    local = E.PackedDataset(xw, yw, cw, mw)
    ef = _make_psum_eval_fn(spec, local, mt, mv, ("data",))
    return ef(Genome(*gt))
ft2, fv2 = f((g.gate_fn, g.edge_src, g.out_src), data.x_words, data.y_words,
             data.class_words, data.mask_words, mtr, mva)
assert np.allclose(ft_ref, ft2) and np.allclose(fv_ref, fv2)
print("psum fitness exact")

cfg = EvolveConfig(lam=4, kappa=150, max_gens=800)
icfg = IslandConfig(migrate_every=16, island_axis="model", data_axes=("data",))
states = evolve_islands(jax.random.split(jax.random.key(0), 4), spec, cfg,
                        icfg, data, mtr, mva, mesh)
bi = best_island(states)
assert float(bi.best_val) > 0.8, float(bi.best_val)
print("islands learned:", round(float(bi.best_val), 3))
""",
        n_devices=8,
    )
    assert "psum fitness exact" in out
    assert "islands learned" in out


def test_sharded_moe_matches_reference():
    out = run_multidevice(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_host_mesh
from repro.models.common import MoEConfig
from repro.models.moe import moe_ffn, moe_ffn_sharded
mesh = make_host_mesh(data=2, model=4)
cfg = MoEConfig(n_experts=8, top_k=2, d_ff_expert=16, capacity_factor=4.0)
ks = jax.random.split(jax.random.key(0), 5)
T, D = 64, 32
x = jax.random.normal(ks[0], (T, D))
router = jax.random.normal(ks[1], (D, 8)) * 0.1
wg, wu = (jax.random.normal(k, (8, D, 16)) * 0.1 for k in ks[2:4])
wd = jax.random.normal(ks[4], (8, 16, D)) * 0.1
y_ref, _ = moe_ffn(x, router, wg, wu, wd, cfg)
with mesh:
    y_sh, _ = jax.jit(lambda *a: moe_ffn_sharded(*a, cfg, mesh, ("data",),
                                                 "model"))(x, router, wg, wu, wd)
assert float(jnp.max(jnp.abs(y_ref - y_sh))) < 1e-5
print("moe sharded ok")
""",
        n_devices=8,
    )
    assert "moe sharded ok" in out


def test_minimesh_train_and_decode_lower_compile():
    """The dry-run machinery on a 2×4 mini-mesh: lower+compile a smoke train
    step and a smoke decode step with the production sharding rules."""
    out = run_multidevice(
        """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.sharding.params import (batch_specs, param_specs,
                                   train_state_specs, tree_shardings)
from repro.sharding.specs import MeshAxes, use_mesh_axes
from repro.train.optimizer import OptConfig
from repro.train.train_step import make_train_step, train_state_shapes

mesh = make_host_mesh(data=2, model=4)
axes = MeshAxes.for_mesh(mesh)
for arch in ("granite-moe-1b-a400m", "rwkv6-7b", "minitron-8b"):
    cfg = get_config(arch).smoke()
    opt = OptConfig(kind=cfg.optimizer)
    sds = train_state_shapes(cfg, opt)
    sh = tree_shardings(mesh, sds, train_state_specs(cfg, axes, opt.kind))
    B, S = 8, 32
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    bsh = tree_shardings(mesh, batch,
                         {k: batch_specs(cfg, axes, "train")[k] for k in batch})
    step = make_train_step(cfg, opt, grad_shardings=sh.params)
    fn = jax.jit(step, in_shardings=(sh, bsh), out_shardings=(sh, None),
                 donate_argnums=(0,))
    with mesh, use_mesh_axes(mesh):
        compiled = fn.lower(sds, batch).compile()
    assert compiled.memory_analysis() is not None
    # decode
    psds = lm.param_shapes(cfg)
    psh = tree_shardings(mesh, psds, param_specs(cfg, axes))
    csds = jax.eval_shape(lambda: lm.init_cache(cfg, 8, 64))
    csh = tree_shardings(mesh, csds, {**lm.cache_specs(cfg, axes), "pos": P()})
    tok = {"token": jax.ShapeDtypeStruct((8, 1), jnp.int32)}
    tsh = tree_shardings(mesh, tok, {"token": P(("data",), None)})
    dfn = jax.jit(lambda p, c, b: lm.decode_step(p, cfg, c, **b),
                  in_shardings=(psh, csh, tsh), out_shardings=(None, csh),
                  donate_argnums=(1,))
    with mesh, use_mesh_axes(mesh):
        dfn.lower(psds, csds, tok).compile()
    print(arch, "mini-mesh ok")
""",
        n_devices=8,
        timeout=1200,
    )
    assert out.count("mini-mesh ok") == 3


def test_compressed_psum_multidevice():
    """int8 EF gradient compression with a real psum over 4 devices."""
    out = run_multidevice(
        """
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.train.grad_compress import quantize_with_feedback
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(data=4)
g = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.3
@partial(jax.shard_map, mesh=mesh, in_specs=P("data", None), out_specs=P("data", None),
         check_vma=False)
def compressed_allreduce(g_loc):
    scale = jax.lax.pmax(jnp.max(jnp.abs(g_loc)), "data") / 127.0
    q, err = quantize_with_feedback(g_loc, jnp.zeros_like(g_loc), scale)
    total = jax.lax.psum(q, "data") * scale / 4.0
    return jnp.broadcast_to(total, g_loc.shape)
out = compressed_allreduce(g)
exact = jnp.mean(g, axis=0)
err = float(jnp.max(jnp.abs(np.asarray(out)[0] - exact)))
scale = float(jnp.max(jnp.abs(g))) / 127.0
assert err <= scale + 1e-6, (err, scale)
print("compressed psum ok, err", err)
""",
        n_devices=4,
    )
    assert "compressed psum ok" in out
