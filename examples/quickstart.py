"""Quickstart: synthesize a proxy-app for a distributed JAX program.

    PYTHONPATH=src python examples/quickstart.py

Traces a halo-exchange stencil (the paper's Fig. 2 pattern) running under
shard_map on 8 devices, compresses the trace to a context-free grammar,
fits TPU basic-block combinations to every compute segment, emits an
executable proxy module, and verifies fidelity + losslessness.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
from jax import shard_map      # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.compat import make_mesh  # noqa: E402
from repro.core.synthesize import synthesize  # noqa: E402

N = 8
mesh = make_mesh((N,), ("x",))


def stencil_step(u, w):
    """12 iterations of: halo exchange -> compute -> global residual."""
    def body(carry, _):
        u, w = carry
        left = jax.lax.ppermute(u[:, :1], "x",
                                [(i, (i + 1) % N) for i in range(N)])
        right = jax.lax.ppermute(u[:, -1:], "x",
                                 [(i, (i - 1) % N) for i in range(N)])
        u = u + 0.1 * (left + right - 2.0 * u)
        for _ in range(3):
            u = jnp.tanh(u @ w)
        residual = jax.lax.psum(jnp.sum(u), "x")
        return (u, w), residual

    (u, _), rs = jax.lax.scan(body, (u, w), None, length=12)
    return u, rs


def main():
    f = shard_map(stencil_step, mesh=mesh,
                  in_specs=(P(None, "x"), P()),
                  out_specs=(P(None, "x"), P()))
    u = jnp.ones((256, 128 * N))
    w = jnp.ones((128, 128)) * 0.01

    result = synthesize(f, u, w, name="stencil_proxy",
                        out_dir="artifacts/proxies")
    print("=== synthesis stats ===")
    for k, v in result.stats.items():
        print(f"  {k}: {v}")

    fid = result.fidelity()
    print("\n=== fidelity (paper Table 3 columns) ===")
    print("  comm lossless:", fid.comm_lossless)
    print(f"  mean relative error: {fid.mean:.4f}")
    print(fid.heatmap_csv())

    print("\n=== replaying all ranks (batched by signature group) ===")
    states = result.proxy.run_all()
    n_groups = len(result.proxy.signature_groups())
    print(f"  {len(states)} ranks replayed in {n_groups} signature group(s)")
    t_batched = result.proxy.time_all(iters=3)
    t_per_rank = result.proxy.time_all(iters=3, batched=False)
    print(f"  full sweep: batched {t_batched*1e3:.2f} ms"
          f" vs per-rank {t_per_rank*1e3:.2f} ms"
          f" ({t_per_rank / max(t_batched, 1e-12):.1f}x)")
    print(f"  single-rank replay: {result.proxy.time_local(0, iters=3)*1e3:.2f} ms")
    print(f"\ngenerated proxy source: {result.proxy.module.__proxy_path__}")


if __name__ == "__main__":
    main()
