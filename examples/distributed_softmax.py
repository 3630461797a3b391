"""The paper's technique distributed: vocab-parallel softmax/logsumexp with a
SINGLE fused (m, n) collective vs the two collectives (max + sum) the
three-pass algorithm needs.  Runs on however many devices jax sees
(XLA_FLAGS=--xla_force_host_platform_device_count=8 to fake 8).

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     PYTHONPATH=src python examples/distributed_softmax.py
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import twopass
from repro.launch.mesh import make_mesh

n_dev = len(jax.devices())
mesh = make_mesh((n_dev,), ("model",))
vocab = 1024 * n_dev
x = jax.random.normal(jax.random.PRNGKey(0), (8, vocab)) * 10

fn = jax.jit(jax.shard_map(
    lambda xl: twopass.twopass_softmax_sharded(xl, "model"),
    mesh=mesh, in_specs=P(None, "model"), out_specs=P(None, "model")))
y = fn(x)
ref = jax.nn.softmax(x, -1)
print(f"devices={n_dev} vocab={vocab}")
print("max |sharded - reference|:", float(jnp.max(jnp.abs(y - ref))))

txt = fn.lower(x).compile().as_text()
n_coll = txt.count("all-gather(") + txt.count("all-reduce(")
print(f"collectives in compiled module: {n_coll} "
      "(three-pass vocab-parallel needs 2: max-allreduce + sum-allreduce)")
