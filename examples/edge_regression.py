"""Distributed edge scenario: every device sketches its local stream, the
sketches merge by integer addition (psum), and every device trains the same
model from the merged sketch — optionally with a differentially-private
release.

The mesh spans every device JAX finds (1 or 4 TPU chips, or the CPU):
    PYTHONPATH=src python examples/edge_regression.py
On the CPU, ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives it
eight devices to split the stream over.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import distributed, dfo, erm, losses, lsh, privacy, sketch
from repro.data import datasets


def main() -> None:
    key = jax.random.PRNGKey(0)
    k_data, k_hash, k_fit, k_priv = jax.random.split(key, 4)

    # One global regression problem, observed as device-local streams.
    x, y, _ = datasets.make_regression(k_data, n=4096, d=8, noise=0.2,
                                       condition=10)
    xs = (x - x.mean(0)) / (x.std(0) + 1e-8)
    ys = (y - y.mean()) / (y.std() + 1e-8)
    # The registered spec owns the data encoding (concat [x, y] for the
    # paired PRP regression loss) — same spine as every other loss.
    spec = losses.PRP_REGRESSION
    z = spec.encode(xs, ys)
    z_scaled, _ = lsh.scale_to_unit_ball(z)

    params = lsh.init_srp(k_hash, rows=2048, planes=4, dim=z.shape[1] + 2)
    mesh = Mesh(np.array(jax.devices()), ("data",))

    # SPMD: local sketch per device + integer all-reduce == merged sketch.
    merged = distributed.sharded_sketch(params, z_scaled, mesh, axis="data")
    print(f"devices: {len(jax.devices())}, merged sketch n={int(merged.n)}, "
          f"bytes={merged.memory_bytes():,}")

    # Every device can now train locally from the merged counters through
    # the generic erm driver (regression.fit is a thin adapter over it).
    res = erm.fit(spec, merged, params, k_fit,
                  dfo_config=dfo.DFOConfig(steps=300, num_queries=8,
                                           sigma=0.5, learning_rate=1.0,
                                           decay=0.995))
    mse = float(jnp.mean((xs @ res.theta[:-1] - ys) ** 2))
    print(f"distributed-sketch model MSE (standardized): {mse:.4f} "
          f"(var ys = {float(jnp.var(ys)):.4f})")

    # Differentially-private release of the merged sketch (eps = 1).
    private = privacy.privatize_counts(k_priv, merged, epsilon=1.0)
    q = lsh.query_codes(params, jnp.zeros(z.shape[1]))
    exact = float(sketch.query(merged, q, paired=True))
    noisy = float(privacy.query_private(private, q, paired=True))
    print(f"query at theta=0: exact={exact:.4f} private(eps=1)={noisy:.4f}")


if __name__ == "__main__":
    main()
