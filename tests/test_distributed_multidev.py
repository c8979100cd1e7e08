"""Multi-device tests (8 fake CPU devices via subprocess — conftest must NOT
force the device count globally): concurrent/distributed factorization,
tree all-reduce, pipeline parallelism, compressed DP, elastic restore."""
import pytest

from _mdev import run_multidevice


@pytest.mark.slow
def test_concurrent_factorize_sharded():
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.data import make_arrowhead
from repro.core import TileGrid, BandedCTSF
from repro.core.concurrent import stack_ctsf, concurrent_factorize, concurrent_logdet
mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
mats, denses = [], []
for s in range(8):
    A, st = make_arrowhead(160, 16, 16, rho=0.5, seed=s)
    bm = BandedCTSF.from_sparse(A, TileGrid(st, t=16))
    mats.append(bm); denses.append(bm.to_dense(lower_only=False))
fac = concurrent_factorize(stack_ctsf(mats), mesh=mesh, axis="data")
lds = concurrent_logdet(fac)
for i in range(8):
    _, ldref = np.linalg.slogdet(denses[i])
    assert abs(float(lds[i]) - ldref) < 1e-2 * abs(ldref), i
print("OK")
""")


@pytest.mark.slow
def test_distributed_single_factorization():
    run_multidevice("""
import numpy as np, jax
from jax.sharding import Mesh
from repro.data import make_arrowhead
from repro.core import TileGrid, BandedCTSF
from repro.core.distributed import partition_banded, distributed_factorize, assemble_factor
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
A, st = make_arrowhead(16*16 + 16, 16, 16, rho=0.0, seed=3)
g = TileGrid(st, t=16)
bm = BandedCTSF.from_sparse(A, g)
pm = partition_banded(bm, 4)
out = distributed_factorize(pm, mesh, axis="model")
f = assemble_factor(out, g)
Lref = np.linalg.cholesky(bm.to_dense(lower_only=False))
assert np.abs(f.ctsf.to_dense() - np.tril(Lref)).max() < 1e-4
print("OK")
""")


@pytest.mark.slow
def test_partition_rejects_coupled_bands():
    run_multidevice("""
from repro.data import make_arrowhead
from repro.core import TileGrid, BandedCTSF
from repro.core.distributed import partition_banded
A, st = make_arrowhead(16*16 + 16, 16, 16, rho=0.7, seed=0)  # coupled!
bm = BandedCTSF.from_sparse(A, TileGrid(st, t=16))
try:
    partition_banded(bm, 2)
    raise SystemExit("should have raised")
except ValueError as e:
    assert "partition boundary" in str(e)
print("OK")
""", n_devices=1)


@pytest.mark.slow
def test_tree_allreduce_matches_psum():
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.sharding.collectives import tree_allreduce, ring_allreduce
mesh = Mesh(np.array(jax.devices()), ("x",))
data = jnp.arange(8 * 5, dtype=jnp.float32).reshape(8, 5)
def f(kind):
    def local(x):
        if kind == "tree": return tree_allreduce(x, "x")
        if kind == "ring": return ring_allreduce(x, "x")
        return jax.lax.psum(x, "x")
    fn = jax.shard_map(local, mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False)
    return np.asarray(jax.jit(fn)(data))
ref = f("psum")
np.testing.assert_allclose(f("tree"), ref, rtol=1e-6)
np.testing.assert_allclose(f("ring"), ref, rtol=1e-6)
print("OK")
""")


@pytest.mark.slow
def test_quantized_allreduce_accuracy():
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.sharding.collectives import quantized_allreduce
mesh = Mesh(np.array(jax.devices()), ("x",))
rng = np.random.default_rng(0)
data = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
def local(x): return quantized_allreduce(x, "x")
fn = jax.shard_map(local, mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False)
got = np.asarray(jax.jit(fn)(data))
ref = np.asarray(data).sum(0, keepdims=True).repeat(8, 0)
err = np.abs(got - ref).max() / np.abs(ref).max()
assert err < 0.02, err   # int8 quantization noise bound
print("OK")
""")


@pytest.mark.slow
def test_pipeline_parallel_fwd_and_grad():
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.sharding.pipeline import pipeline_forward, split_stages
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
rng = np.random.default_rng(0)
Ws = jnp.asarray(rng.standard_normal((8, 16, 16)) * 0.3, jnp.float32)
x = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
def stage_fn(wstack, h):
    def body(h, w): return jnp.tanh(h @ w), None
    return jax.lax.scan(body, h, wstack)[0]
ref = stage_fn(Ws, x)
out = pipeline_forward(stage_fn, split_stages(Ws, 4), x, mesh, axis="model", n_microbatches=4)
assert float(jnp.abs(out - ref).max()) < 1e-5
g1 = jax.grad(lambda w: (pipeline_forward(stage_fn, split_stages(w,4), x, mesh, axis='model', n_microbatches=4)**2).sum())(Ws)
g2 = jax.grad(lambda w: (stage_fn(w, x)**2).sum())(Ws)
assert float(jnp.abs(g1-g2).max()/jnp.abs(g2).max()) < 1e-5
print("OK")
""")


@pytest.mark.slow
def test_compressed_dp_trains():
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.runtime.dp_compressed import make_compressed_dp_step
from repro.optim.adamw import adamw_init
mesh = Mesh(np.array(jax.devices()).reshape(8, 1), ("data", "model"))
rng = np.random.default_rng(0)
def loss_fn(params, batch):
    return jnp.mean((batch["x"] @ params["w"] - batch["y"])**2)
params = {"w": jnp.asarray(rng.standard_normal((8, 1)) * 0.1, jnp.float32)}
batch = {"x": jnp.asarray(rng.standard_normal((32, 8)), jnp.float32),
         "y": jnp.asarray(rng.standard_normal((32, 1)), jnp.float32)}
step, ef_init_fn = make_compressed_dp_step(loss_fn, mesh, axis="data", lr=0.05)
state = (params, adamw_init(params), ef_init_fn(params))
l0 = None
for i in range(30):
    state, m = step(state, batch)
    if l0 is None: l0 = float(m["loss"])
assert float(m["loss"]) < 0.9 * l0
print("OK")
""")


@pytest.mark.slow
def test_elastic_restore_across_meshes():
    """Checkpoint written on a 4-device mesh restores onto 8 devices."""
    run_multidevice("""
import numpy as np, jax, jax.numpy as jnp, tempfile, os
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.checkpoint.checkpointer import Checkpointer
devs = jax.devices()
mesh4 = Mesh(np.array(devs[:4]), ("data",))
mesh8 = Mesh(np.array(devs), ("data",))
state = {"w": jax.device_put(jnp.arange(32.0).reshape(8, 4),
                             NamedSharding(mesh4, P("data")))}
d = tempfile.mkdtemp()
ck = Checkpointer(d, async_save=False)
ck.save(3, state)
sh8 = {"w": NamedSharding(mesh8, P("data"))}
out = ck.restore(state, shardings=sh8)
assert out["w"].sharding == sh8["w"]
np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(state["w"]))
print("OK")
""")
