"""Calibrated noise models for variability-aware replay (ROADMAP item).

Cornebize & Legrand (PAPERS.md, arxiv 2102.07674) show that platform
variability — not model error — dominates MPI performance-prediction
error: a point-estimate δ̄ can pass while the proxy's timing
*distribution* is wrong.  This module closes that gap with per-terminal
multiplicative noise calibrated from the variance already present in a
:class:`~repro.core.trace_ir.TraceStore`:

* **compute terminals** draw a mean-one lognormal factor whose σ is the
  log-magnitude spread of the terminal's cluster members;
* **comm terminals** draw a *shifted* lognormal — collectives have a
  deterministic bandwidth floor, so only the fraction ``1 - shift`` of
  the cost fluctuates (``shift`` defaults to :data:`COMM_SHIFT`).

The factor for params ``(σ, shift)`` is

    f = shift + (1 - shift) · exp(σ·z - σ²/2),   z ~ N(0, 1)

which has mean exactly 1 (the lognormal mean-correction term ``-σ²/2``),
is strictly positive, and has variance ``(1-shift)²·(exp(σ²)-1)`` —
monotone in σ, which the property tests pin.

Calibrated params are persisted into generated proxy modules as a
``NOISE_MODELS`` table next to ``TERMINALS`` (both codegen flavors) and
lowered by :class:`~repro.core.progtable.ProgramTable` / the unrolled
emitter through the shared :func:`lower_params`/:func:`perturb` helpers,
so both flavors execute the *identical* split/sample/accumulate op
sequence and stay bit-compatible.

Noise is **default-off and trace-time gated**: :func:`perturb` is a
Python-level no-op unless the replay state carries :data:`NOISE_KEY`
(attached by :func:`attach` when ``ProxyProgram.*(noise=NoiseConfig)``
is used), so ``noise=None`` replay produces byte-identical jaxprs — and
therefore bit-identical δ̄ — to a build without this module.

δ̄ itself is measured by the static jaxpr walker and cannot see runtime
randomness; the noisy path instead *accumulates* each terminal's
perturbed cost into dedicated state leaves (:data:`NOISE_COMPUTE`,
:data:`NOISE_COMM`) during execution, and
:class:`FidelityDistribution` summarizes the per-replica δ̄ of those
executed totals.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.core.events import (CommEvent, ComputeEvent, N_METRICS,
                               cluster_vectors)

# State-dict keys for the noise leaves threaded through replay.  Plain
# dict-key presence (not a flag) is the gate: every rolled control-flow
# construct in progtable carries the whole state pytree, so the key leaf
# threads through scan/switch/fori for free.
NOISE_KEY = "_noise_key"
NOISE_COMPUTE = "_noise_compute"
NOISE_COMM = "_noise_comm"

#: σ floor applied to every calibrated terminal.  Cornebize & Legrand
#: measure ≥1-2% run-to-run variability even on quiesced clusters, so a
#: terminal whose cluster happens to be variance-free in the trace still
#: perturbs at this floor instead of degenerating to a point mass.
SIGMA_FLOOR = 0.01

#: Deterministic fraction of a collective's cost (bandwidth floor).
#: Only ``1 - COMM_SHIFT`` of a comm terminal's payload fluctuates.
COMM_SHIFT = 0.8


# ---------------------------------------------------------------------------
# Sampling + lowering (shared by both codegen flavors)
# ---------------------------------------------------------------------------


def sample_factor(key, sigma: float, shift: float):
    """One mean-one noise factor: ``shift + (1-shift)·exp(σ·z - σ²/2)``."""
    import jax
    import jax.numpy as jnp

    z = jax.random.normal(key, (), jnp.float32)
    sigma = jnp.float32(sigma)
    shift = jnp.float32(shift)
    return shift + (jnp.float32(1.0) - shift) * jnp.exp(
        sigma * z - sigma * sigma * jnp.float32(0.5))


def factor_variance(sigma: float, shift: float) -> float:
    """Closed-form variance of :func:`sample_factor` draws."""
    return (1.0 - shift) ** 2 * (math.exp(sigma * sigma) - 1.0)


@dataclasses.dataclass(frozen=True)
class LoweredNoise:
    """One terminal's noise params bound to its deterministic cost.

    ``cost`` is the terminal's 6-metric compute cost vector (None for
    comm terminals); ``comm_bytes`` its collective payload (0.0 for
    compute terminals).  :func:`perturb` adds ``factor · cost`` /
    ``factor · comm_bytes`` to the state accumulators.
    """
    sigma: float
    shift: float
    cost: tuple | None
    comm_bytes: float


def _desc_cost(desc) -> tuple[tuple | None, float]:
    """(cost_vec, comm_bytes) from one terminal descriptor.

    Accepts both the table flavor's ``TERMINALS`` entries —
    ``('comm', buf, params)`` / ``('compute', x, unroll)`` — and the
    unrolled flavor's compact ``_NOISE_DESCS`` form ``('comm', bytes)``.
    """
    kind = desc[0]
    if kind == "compute":
        # lazy: blocks pulls in jax, and calibration (the only noise entry
        # point the corpus-ingest worker pool touches) never lowers costs
        from repro.core import blocks
        _, x, unroll = desc
        vec = blocks.combo_cost(np.asarray(x, dtype=np.float64), int(unroll))
        return tuple(float(v) for v in vec), 0.0
    if kind != "comm":
        raise ValueError(f"unknown terminal descriptor kind {kind!r}")
    if len(desc) == 2:                      # ('comm', payload_bytes)
        return None, float(desc[1])
    _, _buf, params = desc                  # table flavor descriptor
    ev = CommEvent(kind=params["kind"], shape=tuple(params["shape"]),
                   dtype=params["dtype"], axes=tuple(params["axes"]),
                   detail=tuple(params.get("detail", ())))
    return None, float(ev.payload_bytes)


def lower_params(noise_models, descs) -> tuple[LoweredNoise, ...]:
    """Bind per-terminal ``(σ, shift)`` pairs to terminal costs.

    ``noise_models`` is the emitted ``NOISE_MODELS`` table (one pair per
    terminal, aligned with ``TERMINALS``); ``descs`` the matching
    descriptor tuple (either flavor's form — see :func:`_desc_cost`).
    """
    if len(noise_models) != len(descs):
        raise ValueError("NOISE_MODELS/terminal descriptor length mismatch: "
                         f"{len(noise_models)} vs {len(descs)}")
    out = []
    for (sigma, shift), desc in zip(noise_models, descs):
        cost, cbytes = _desc_cost(desc)
        out.append(LoweredNoise(float(sigma), float(shift), cost, cbytes))
    return tuple(out)


def perturb(st: dict, nz: LoweredNoise | None) -> dict:
    """Accumulate one perturbed terminal cost; no-op without a noise key.

    The gate is Python-level dict-key presence at trace time, so
    ``noise=None`` replay traces byte-identical jaxprs.  Every terminal
    occurrence — comm *and* compute — consumes exactly one key split,
    keeping the random stream aligned between codegen flavors and
    between straight-line and scan/switch lowerings.
    """
    if nz is None or NOISE_KEY not in st:
        return st
    import jax
    import jax.numpy as jnp

    st = dict(st)
    key, sub = jax.random.split(st[NOISE_KEY])
    st[NOISE_KEY] = key
    f = sample_factor(sub, nz.sigma, nz.shift)
    if nz.cost is not None:
        st[NOISE_COMPUTE] = st[NOISE_COMPUTE] + f * jnp.asarray(
            nz.cost, jnp.float32)
    else:
        st[NOISE_COMM] = st[NOISE_COMM] + f * jnp.float32(nz.comm_bytes)
    return st


def attach(st: dict, key) -> dict:
    """Return a copy of a replay state with the noise leaves attached.

    ``key`` must be a raw ``uint32[2]`` PRNG key (not a typed key array)
    so the leaves stay plain arrays under ``shard_map``/``tree``.
    """
    import jax.numpy as jnp

    st = dict(st)
    st[NOISE_KEY] = jnp.asarray(key, jnp.uint32)
    st[NOISE_COMPUTE] = jnp.zeros((N_METRICS,), jnp.float32)
    st[NOISE_COMM] = jnp.zeros((), jnp.float32)
    return st


def replica_key(seed: int, rep_rank: int, replica: int):
    """Per-(seed, group-representative, replica) PRNG key.

    Derived only from logical identifiers — never from device placement —
    so LocalSim and mesh replay draw identical streams by construction.
    """
    import jax

    key = jax.random.PRNGKey(seed)
    key = jax.random.fold_in(key, rep_rank)
    return jax.random.fold_in(key, replica)


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NoiseModel:
    """Calibrated per-cluster / per-comm-kind noise parameters.

    ``compute_sigmas`` maps cluster id → lognormal σ; ``comm_params``
    maps collective kind → ``(σ, shift)``.  Pure data — JSON
    round-trips exactly (:meth:`to_json`/:meth:`from_json`) and rides
    the corpus-store manifest.
    """
    compute_sigmas: dict[int, float]
    comm_params: dict[str, tuple[float, float]]
    sigma_floor: float = SIGMA_FLOOR

    def terminal_params(self, events) -> tuple[tuple[float, float], ...]:
        """Per-terminal ``(σ, shift)`` aligned with a terminal table.

        ``events`` is the merged terminal table's event list (one
        :class:`CommEvent`/:class:`ComputeEvent` per terminal id).
        """
        out = []
        for ev in events:
            if isinstance(ev, CommEvent):
                out.append(self.comm_params.get(
                    ev.kind, (self.sigma_floor, COMM_SHIFT)))
            else:
                out.append((self.compute_sigmas.get(
                    ev.cluster_id, self.sigma_floor), 0.0))
        return tuple(out)

    def to_json(self) -> dict:
        return {
            "compute_sigmas": {str(k): v
                               for k, v in sorted(self.compute_sigmas.items())},
            "comm_params": {k: list(v)
                            for k, v in sorted(self.comm_params.items())},
            "sigma_floor": self.sigma_floor,
        }

    @classmethod
    def from_json(cls, data: dict) -> "NoiseModel":
        return cls(
            compute_sigmas={int(k): float(v)
                            for k, v in data["compute_sigmas"].items()},
            comm_params={k: (float(v[0]), float(v[1]))
                         for k, v in data["comm_params"].items()},
            sigma_floor=float(data.get("sigma_floor", SIGMA_FLOOR)),
        )


def _log_sigma(mags: np.ndarray, floor: float) -> float:
    """σ of log-magnitudes, floored; degenerate samples collapse to floor."""
    mags = np.asarray(mags, dtype=np.float64)
    mags = mags[mags > 0]
    if mags.size < 2:
        return float(floor)
    return float(max(np.std(np.log(mags)), floor))


def _weighted_log_sigma(mags: np.ndarray, weights: np.ndarray,
                        floor: float) -> float:
    """Occurrence-weighted σ of log payloads for one collective kind."""
    mags = np.asarray(mags, dtype=np.float64)
    weights = np.asarray(weights, dtype=np.float64)
    good = (mags > 0) & (weights > 0)
    mags, weights = mags[good], weights[good]
    if mags.size == 0 or weights.sum() <= 0:
        return float(floor)
    logs = np.log(mags)
    mean = np.average(logs, weights=weights)
    var = np.average((logs - mean) ** 2, weights=weights)
    return float(max(math.sqrt(var), floor))


def calibrate(store, cluster_ids: np.ndarray | None = None,
              rel_tol: float = 0.05, sigma_floor: float = SIGMA_FLOOR,
              comm_shift: float = COMM_SHIFT) -> NoiseModel:
    """Calibrate a :class:`NoiseModel` from a columnar TraceStore.

    Compute σ per cluster is the spread of log row-magnitudes
    (``metrics.sum(axis=1)``) over the cluster's member events — the
    intra-cluster variance the rel_tol clustering deliberately collapses
    into one representative.  ``cluster_ids`` defaults to the store's
    own :func:`~repro.core.events.cluster_vectors` assignment (matching
    ``compress_store``); corpus synthesis passes the *joint* assignment
    slice instead so batch and incremental paths calibrate identically.

    Comm σ per collective kind is the occurrence-weighted spread of log
    payload bytes across the kind's comm-pool entries (weights from
    :meth:`~repro.core.trace_ir.TraceStore.comm_occurrence_counts`);
    the shift is the constant bandwidth floor ``comm_shift``.
    """
    metrics = np.asarray(store.metrics, dtype=np.float64)
    if cluster_ids is None:
        cluster_ids, _ = cluster_vectors(metrics, rel_tol)
    cluster_ids = np.asarray(cluster_ids)
    if len(cluster_ids) != len(metrics):
        raise ValueError("cluster_ids length does not match compute events: "
                         f"{len(cluster_ids)} vs {len(metrics)}")

    compute_sigmas: dict[int, float] = {}
    mags = metrics.sum(axis=1)
    for cid in np.unique(cluster_ids):
        compute_sigmas[int(cid)] = _log_sigma(mags[cluster_ids == cid],
                                              sigma_floor)

    counts = store.comm_occurrence_counts()
    by_kind: dict[str, list[tuple[float, float]]] = {}
    for ev, cnt in zip(store.comm_pool, counts):
        by_kind.setdefault(ev.kind, []).append(
            (float(ev.payload_bytes), float(cnt)))
    comm_params = {
        kind: (_weighted_log_sigma(np.array([m for m, _ in pairs]),
                                   np.array([w for _, w in pairs]),
                                   sigma_floor), comm_shift)
        for kind, pairs in by_kind.items()
    }
    return NoiseModel(compute_sigmas=compute_sigmas, comm_params=comm_params,
                      sigma_floor=sigma_floor)


def calibrate_trace(trace, rel_tol: float = 0.05,
                    sigma_floor: float = SIGMA_FLOOR,
                    comm_shift: float = COMM_SHIFT) -> NoiseModel:
    """Calibrate directly from one template :class:`~repro.core.tracer.Trace`
    (single-rank convenience wrapper; same math as :func:`calibrate`)."""
    metrics = trace.compute_metrics_array()
    cluster_ids, _ = cluster_vectors(metrics, rel_tol)
    compute_sigmas: dict[int, float] = {}
    mags = metrics.sum(axis=1)
    for cid in np.unique(cluster_ids):
        compute_sigmas[int(cid)] = _log_sigma(mags[cluster_ids == cid],
                                              sigma_floor)
    by_kind: dict[str, list[tuple[float, float]]] = {}
    for ev in trace.comm_events():
        by_kind.setdefault(ev.kind, []).append((float(ev.payload_bytes), 1.0))
    comm_params = {
        kind: (_weighted_log_sigma(np.array([m for m, _ in pairs]),
                                   np.array([w for _, w in pairs]),
                                   sigma_floor), comm_shift)
        for kind, pairs in by_kind.items()
    }
    return NoiseModel(compute_sigmas=compute_sigmas, comm_params=comm_params,
                      sigma_floor=sigma_floor)


# ---------------------------------------------------------------------------
# Replay-facing config + distribution summary
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NoiseConfig:
    """Opt-in switch for noisy replay: ``ProxyProgram.*(noise=NoiseConfig())``.

    ``n_replicas`` seeded replicas run as ONE extra vmapped axis per
    signature group, so the sweep scheduler and compile caches are
    reused; keys derive from ``(seed, group-representative, replica)``
    and are placement-invariant (LocalSim ≡ mesh bit-for-bit).
    """
    seed: int = 0
    n_replicas: int = 8

    def __post_init__(self):
        if self.n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")


@dataclasses.dataclass(frozen=True)
class FidelityDistribution:
    """δ̄ as a distribution over seeded noisy replicas (paper eq. 8 +
    Cornebize-style variability bands).

    ``replica_delta`` is the raw ``(n_replicas, n_metrics, n_ranks)``
    per-replica δ matrix; everything else is a deterministic summary of
    it (normal-approximation ``mean ± z·std`` bands — no resampling, so
    the whole object is a pure function of ``(seed, n_replicas)``).
    """
    replica_delta: np.ndarray        # (n_replicas, n_metrics, n_ranks)
    comm_bytes: np.ndarray           # (n_replicas, n_ranks) perturbed totals
    ranks: tuple[int, ...]
    seed: int
    n_replicas: int
    comm_lossless: bool
    mesh_checked: bool = False

    @property
    def delta_mean(self) -> np.ndarray:
        """(n_metrics, n_ranks) mean δ over replicas."""
        return self.replica_delta.mean(axis=0)

    @property
    def delta_std(self) -> np.ndarray:
        """(n_metrics, n_ranks) std of δ over replicas."""
        return self.replica_delta.std(axis=0)

    @property
    def replica_means(self) -> np.ndarray:
        """(n_replicas,) scalar δ̄ per replica."""
        return self.replica_delta.mean(axis=(1, 2))

    @property
    def mean(self) -> float:
        """Mean δ̄ over replicas (the noisy analog of FidelityReport.mean)."""
        return float(self.replica_means.mean())

    @property
    def std(self) -> float:
        return float(self.replica_means.std())

    def ci(self, z: float = 1.96) -> tuple[float, float]:
        """Normal-approx confidence band for the scalar δ̄."""
        return (self.mean - z * self.std, self.mean + z * self.std)

    def metric_bands(self, z: float = 1.96) -> np.ndarray:
        """(n_metrics, 2) per-metric [lo, hi] bands over replicas."""
        per_rep = self.replica_delta.mean(axis=2)      # (n_replicas, n_metrics)
        mean, std = per_rep.mean(axis=0), per_rep.std(axis=0)
        return np.stack([mean - z * std, mean + z * std], axis=1)

    def to_csv(self) -> str:
        """Mean-δ heatmap CSV with seed/replica provenance headers."""
        from repro.core.events import METRIC_NAMES

        lines = [f"# seed={self.seed}", f"# n_replicas={self.n_replicas}",
                 "metric," + ",".join(f"rank{p}" for p in self.ranks)]
        mean = self.delta_mean
        for m, mname in enumerate(METRIC_NAMES):
            lines.append(mname + "," +
                         ",".join(f"{v:.4f}" for v in mean[m]))
        return "\n".join(lines)


def parse_fidelity_csv(text: str) -> tuple[dict, np.ndarray]:
    """Parse :meth:`FidelityDistribution.to_csv` /
    ``FidelityReport.to_csv`` output back into ``(meta, delta)`` where
    ``meta`` carries the provenance header fields and ``delta`` is the
    ``(n_metrics, n_ranks)`` float matrix — the round-trip oracle for
    the provenance-header regression test."""
    meta: dict = {}
    rows = []
    for line in text.strip().splitlines():
        if line.startswith("#"):
            k, _, v = line.lstrip("# ").partition("=")
            meta[k.strip()] = int(v)
        elif line.startswith("metric,"):
            meta["ranks"] = tuple(
                int(c[len("rank"):]) for c in line.split(",")[1:])
        else:
            rows.append([float(v) for v in line.split(",")[1:]])
    return meta, np.asarray(rows, dtype=np.float64)
