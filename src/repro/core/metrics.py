"""Per-equation TPU cost model -- the PAPI-counter analog (DESIGN.md §2).

The paper reads 6 hardware counters around every MPI call.  On TPU the staged
jaxpr gives *exact* op counts without any runtime interference, so each jaxpr
equation is mapped to a 6-metric cost vector:

    mxu_flops, vpu_elems, hbm_bytes, transcendentals, gather_elems, scan_steps

``hbm_bytes`` is deliberately fusion-agnostic (operands + results per
equation): the same convention is applied to the target program and to the
proxy basic blocks, so the QP fit (paper eq. 6-7) is self-consistent.  The
roofline analysis uses XLA's own ``cost_analysis`` instead -- see
:mod:`repro.launch.roofline`.
"""
from __future__ import annotations

import math
from typing import Any

import numpy as np

from repro.core.events import METRIC_NAMES, N_METRICS, dtype_bytes

# --- metric indices ---------------------------------------------------------
I_MXU, I_VPU, I_BYTES, I_TRANS, I_GATHER, I_SCAN = range(N_METRICS)

#: primitives whose elementwise application hits the VPU slow path
TRANSCENDENTAL_PRIMS = {
    "exp", "exp2", "expm1", "log", "log1p", "log2", "tanh", "tan", "sin",
    "cos", "asin", "acos", "atan", "atan2", "sinh", "cosh", "asinh", "acosh",
    "atanh", "erf", "erfc", "erf_inv", "logistic", "pow", "integer_pow",
    "rsqrt", "sqrt", "cbrt", "digamma", "lgamma", "regularized_incomplete_beta",
}

#: irregular-address primitives (the L1_DCM analog).  The combining
#: scatters (``scatter-add``/``-mul``/``-min``/``-max``) are not listed: they
#: are costed as generic elementwise, the convention the fidelity baseline
#: was recorded under (ROADMAP 2.4).
GATHER_PRIMS = {"gather", "scatter", "dynamic_slice", "dynamic_update_slice",
                "take", "take_along_axis", "argsort", "sort", "top_k"}

#: primitives that move data without arithmetic (count bytes only)
DATA_MOVEMENT_PRIMS = {
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "concatenate",
    "slice", "pad", "rev", "convert_element_type", "bitcast_convert_type",
    "copy", "device_put", "iota", "split", "expand_dims",
    "pvary", "sharding_constraint", "reshard",
}

#: zero-cost bookkeeping primitives (``pvary`` is the replication marker
#: shard_map's check_vma machinery inserts; it lowers to a no-op and must
#: not be recorded as a communication event).
FREE_PRIMS = {
    "stop_gradient", "axis_index", "sharding_cast", "pvary",
    "symbolic_zeros", "empty", "debug_callback", "name",
    "optimization_barrier",
}

#: jaxpr collective primitive name -> CommEvent kind
COLLECTIVE_PRIMS = {
    "psum": "psum",
    "psum_invariant": "psum",
    "psum2": "psum",
    "pmax": "pmax",
    "pmin": "pmin",
    "all_gather": "all_gather",
    "all_gather_invariant": "all_gather",
    "reduce_scatter": "reduce_scatter",
    "all_to_all": "all_to_all",
    "ppermute": "ppermute",
}

#: call-like primitives whose body is ``params["jaxpr"]`` (a nested
#: ``jax.jit`` is ``jit``, ``jax.checkpoint`` is ``remat2``)
CALL_PRIMS = {"jit", "closed_call", "remat2"}

#: custom-derivative primitives whose primal body is ``params["call_jaxpr"]``
CUSTOM_DIFF_PRIMS = {"custom_jvp_call", "custom_vjp_call"}

#: every primitive carrying sub-jaxprs that the walker enters.  A primitive
#: with a sub-jaxpr that is not listed here raises in :func:`eqn_cost`:
#: costing it as one elementwise op would drop its collectives silently.
HIGHER_ORDER_PRIMS = CALL_PRIMS | CUSTOM_DIFF_PRIMS | {
    "shard_map", "scan", "while", "cond",
}


#: sub-jaxpr params that are scalar combiners, not program bodies
COMBINER_PARAMS = {"update_jaxpr"}


def _has_sub_jaxpr(eqn) -> bool:
    for k, v in eqn.params.items():
        if k in COMBINER_PARAMS:
            continue
        if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
            return True
        if isinstance(v, (tuple, list)) and any(
                hasattr(b, "eqns") or hasattr(b, "jaxpr") for b in v):
            return True
    return False


def _aval_size(aval) -> int:
    try:
        return int(np.prod(aval.shape)) if aval.shape else 1
    except Exception:
        return 1


def _aval_bytes(aval) -> int:
    try:
        return _aval_size(aval) * dtype_bytes(aval.dtype)
    except Exception:
        return 0


def eqn_io_bytes(eqn) -> int:
    """Fusion-agnostic bytes: all operands + all results."""
    total = 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            total += _aval_bytes(aval)
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            total += _aval_bytes(aval)
    return total


def dot_general_flops(eqn) -> int:
    """2*M*N*K*batch flops for a dot_general from its dimension numbers."""
    (lhs_c, rhs_c), (lhs_b, rhs_b) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = math.prod(lhs[d] for d in lhs_b) if lhs_b else 1
    k = math.prod(lhs[d] for d in lhs_c) if lhs_c else 1
    m = math.prod(lhs[d] for d in range(len(lhs)) if d not in lhs_b and d not in lhs_c)
    n = math.prod(rhs[d] for d in range(len(rhs)) if d not in rhs_b and d not in rhs_c)
    return 2 * batch * m * n * k


def conv_flops(eqn) -> int:
    """2 * out_elems * (in_channels/groups) * prod(kernel_spatial)."""
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    dnums = eqn.params["dimension_numbers"]
    groups = eqn.params.get("feature_group_count", 1)
    # rhs layout: (out_ch, in_ch/groups, *spatial) permuted by dnums.rhs_spec
    rhs_spec = dnums.rhs_spec  # (out_ch_dim, in_ch_dim, *spatial_dims)
    in_ch = rhs[rhs_spec[1]]
    kernel_spatial = math.prod(rhs[d] for d in rhs_spec[2:])
    return 2 * math.prod(out) * in_ch * kernel_spatial // max(groups, 1)


def eqn_cost(eqn) -> np.ndarray:
    """6-metric cost vector for a single *first-order* equation."""
    c = np.zeros(N_METRICS, dtype=np.float64)
    name = eqn.primitive.name
    if name in FREE_PRIMS:
        return c
    out_elems = sum(_aval_size(v.aval) for v in eqn.outvars
                    if hasattr(getattr(v, "aval", None), "shape"))
    c[I_BYTES] = eqn_io_bytes(eqn)
    if name == "dot_general":
        c[I_MXU] = dot_general_flops(eqn)
    elif name == "conv_general_dilated":
        c[I_MXU] = conv_flops(eqn)
    elif name in TRANSCENDENTAL_PRIMS:
        c[I_TRANS] = out_elems
        c[I_VPU] = out_elems
    elif name in GATHER_PRIMS:
        c[I_GATHER] = out_elems
        c[I_VPU] = out_elems  # address computation
    elif name in DATA_MOVEMENT_PRIMS:
        pass  # bytes only
    elif name.startswith("reduce_") or name in ("argmax", "argmin", "reduce"):
        in_elems = sum(_aval_size(v.aval) for v in eqn.invars
                       if hasattr(getattr(v, "aval", None), "shape"))
        c[I_VPU] = in_elems
    elif name == "cumsum" or name.startswith("cum"):
        in_elems = sum(_aval_size(v.aval) for v in eqn.invars
                       if hasattr(getattr(v, "aval", None), "shape"))
        c[I_VPU] = in_elems
    elif _has_sub_jaxpr(eqn):
        raise NotImplementedError(
            f"jaxpr walker: primitive {name!r} carries a sub-jaxpr the walker "
            "does not know; add it to metrics.HIGHER_ORDER_PRIMS and walk it")
    else:
        # generic elementwise (add/mul/select/compare/min/max/...)
        c[I_VPU] = out_elems
    return c


def collective_event_info(eqn) -> dict[str, Any]:
    """Extract CommEvent fields from a collective equation."""
    name = eqn.primitive.name
    kind = COLLECTIVE_PRIMS[name]
    aval = eqn.invars[0].aval
    shape = tuple(int(s) for s in aval.shape)
    dtype = str(np.dtype(aval.dtype).name) if hasattr(aval, "dtype") else "float32"
    ax = eqn.params.get("axis_name", eqn.params.get("axes", ()))
    if isinstance(ax, str):
        ax = (ax,)
    axes = tuple(str(a) for a in ax)
    detail: tuple = ()
    if kind == "ppermute":
        detail = ("rawperm", tuple(tuple(p) for p in eqn.params.get("perm", ())))
    elif kind == "all_to_all":
        detail = (int(eqn.params.get("split_axis", 0)), int(eqn.params.get("concat_axis", 0)))
    elif kind == "all_gather":
        detail = (int(eqn.params.get("all_gather_dimension", 0)),)
    elif kind == "reduce_scatter":
        detail = (int(eqn.params.get("scatter_dimension", 0)),)
    groups = eqn.params.get("axis_index_groups")
    if groups is not None:
        detail = detail + ("groups", tuple(tuple(g) for g in groups))
    return dict(kind=kind, shape=shape, dtype=dtype, axes=axes, detail=detail)


# ---------------------------------------------------------------------------
# Roofline-style time estimate for one event (used to apportion measured wall
# time over compute events, and by the ScalaBench-style baseline).
# ---------------------------------------------------------------------------

# TPU v5e-class chip constants (per the assignment):
PEAK_FLOPS_BF16 = 197e12     # FLOP/s
HBM_BW = 819e9               # B/s
ICI_BW = 50e9                # B/s per link
VPU_RATE = 4e12              # elem-ops/s (8x128 lanes * ~4 GHz, order-of-magnitude)
TRANS_RATE = 0.5e12          # transcendental ops/s (slow path)
GATHER_RATE = 0.25e12        # irregular elems/s
SCAN_OVERHEAD = 1e-7         # s per sequential step (amortized TPU loop bookkeeping)


def roofline_seconds(vec: np.ndarray) -> float:
    """max-of-terms execution-time estimate for a 6-metric vector."""
    return max(
        vec[I_MXU] / PEAK_FLOPS_BF16,
        vec[I_BYTES] / HBM_BW,
        vec[I_VPU] / VPU_RATE,
        vec[I_TRANS] / TRANS_RATE,
        vec[I_GATHER] / GATHER_RATE,
        vec[I_SCAN] * SCAN_OVERHEAD,
    )


def comm_seconds(payload_bytes: int, n_devices: int = 2) -> float:
    """alpha-beta estimate for a collective (ring, bidirectional ICI)."""
    return 1e-6 + payload_bytes * max(n_devices - 1, 1) / (n_devices * ICI_BW)


def pretty_vector(vec: np.ndarray) -> str:
    return ", ".join(f"{n}={v:.3g}" for n, v in zip(METRIC_NAMES, vec))
