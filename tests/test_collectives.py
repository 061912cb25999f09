"""int8-compressed gradient all-reduce: numerics + bandwidth accounting."""
from tests._subproc import run_py


def test_compressed_psum_numerics():
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.distributed.collectives import compressed_psum
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
g = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)

def f(x):
    return compressed_psum(x, "data")

y = shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"))(g)
# each shard returns the int8-compressed mean over shards
expect = np.broadcast_to(np.asarray(g).mean(axis=0, keepdims=True), (8, 64))
got = np.asarray(y)
rel = np.abs(got - expect).max() / (np.abs(expect).max() + 1e-9)
assert rel < 0.02, rel  # int8 quantization error bound
print("COMPRESSED_OK", rel)
""", devices=8)
    assert "COMPRESSED_OK" in out


def test_compressed_dp_grads_close_to_exact():
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.collectives import make_compressed_dp_grad_fn
mesh = jax.make_mesh((8,), ("data",))
W = jnp.asarray(np.random.default_rng(1).standard_normal((16, 4)), jnp.float32)
x = jnp.asarray(np.random.default_rng(2).standard_normal((32, 16)), jnp.float32)
y = jnp.asarray(np.random.default_rng(3).standard_normal((32, 4)), jnp.float32)

def loss(w, batch):
    xx, yy = batch
    return jnp.mean((xx @ w - yy) ** 2)

f = make_compressed_dp_grad_fn(loss, mesh, "data")
l1, g1 = f(W, (x, y))
l2, g2 = jax.value_and_grad(loss)(W, (x, y))
rel = float(jnp.abs(g1 - g2).max() / (jnp.abs(g2).max() + 1e-9))
assert rel < 0.05, rel
print("DPGRAD_OK", rel)
""", devices=8)
    assert "DPGRAD_OK" in out
