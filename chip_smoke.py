"""Bring-up smoke run of the main path on TPU chips: run a program, trace it,
synthesize its proxy, replay the proxy on the chip, compare.

    python chip_smoke.py               # one chip, phases 1-5
    python chip_smoke.py --chips 4     # four chips: mesh-sharded replay only

One chip (mamba2-2.7b at its published width, random weights from --seed):

1. device: the first JAX device must be a TPU; there is no CPU fallback;
2. original: the bf16 decode step at batch 8, parameters and decode state
   generated on the device, checked finite and against the CPU on the
   reduced config;
3. synthesize: the same step traced from shapes only; the walker's MXU
   flops must equal the step's dense matmul count;
4. replay: the proxy run and timed on the chip beside the original;
5. corpus: the five zoo scenarios synthesized in memory with one batched
   PGD fit on the chip; every scenario must stay comm-lossless and within
   ``artifacts/fidelity_baseline.json``.

``--chips 4`` runs the stencil program of ``benchmarks/common.py`` on the
four chips, synthesizes it, and replays it mesh-sharded (real collectives
on the chips) and on LocalSim: δ̄ and per-rank collective sequences must be
identical and every group's outputs must sit on its planned devices.

All phases run in this one process (the chip belongs to one process).  Any
failure exits non-zero; only a full pass prints the closing JSON line
``{"ok": true, "device": {...}}``.  Times are bring-up observations, not
benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

BATCH = 8                 # decode batch
ARCH = "mamba2-2.7b"
TIMED_STEPS = 5             # timed steps of each original program
PROXY_ITERS = 5
REF_TOL = 1e-3            # CPU-vs-chip logits agreement, relative to max |logit|


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def setup_paths() -> None:
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit("chip_smoke: src/repro not found next to this script; run it "
                 "from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))


def phase_device(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devs[0].platform!r}); there is no CPU fallback")
    check(len(devs) >= n_chips, f"--chips {n_chips} but JAX sees {len(devs)}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"[1 device] platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    return device


def random_tree(abstract, seed: int, scale: float = 0.1):
    """Device-generated N(0, scale) leaves shaped like ``abstract``."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(abstract)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def draw(k, sd):
        return jax.jit(lambda k: (jax.random.normal(k, sd.shape, jnp.float32)
                                  * scale).astype(sd.dtype))(k)

    return jax.tree.unflatten(treedef, [draw(k, sd)
                                        for k, sd in zip(keys, leaves)])


def decode_inputs(cfg, seed: int):
    """(params, cache, batch, pos) for one decode step, all on the device."""
    import jax
    import jax.numpy as jnp
    from repro.models.model import abstract_cache, init_params

    params = init_params(cfg, seed)
    cache = random_tree(abstract_cache(cfg, BATCH, 1), seed + 1)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 2), (BATCH, 1), 0,
                                cfg.vocab, jnp.int32)
    return params, cache, {"tokens": tokens}, jnp.int32(0)


def decode_step_fn(cfg):
    from repro.models.model import build_forward
    decode = build_forward(cfg, "decode")
    return lambda params, cache, batch, pos: decode(params, cache, batch, pos,
                                                    cfg)


def decode_mxu_flops(cfg, b: int) -> float:
    """Dense matmul flops of one mamba2 decode step: in_proj, the causal
    conv, the state readout and out_proj per layer, plus the LM head."""
    from repro.models.ssm import CONV_W
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    h = d_in // cfg.ssm_head_dim
    gn = cfg.ssm_groups * cfg.ssm_state
    per_layer = (d * (2 * d_in + 2 * gn + h) + CONV_W * (d_in + 2 * gn)
                 + h * cfg.ssm_head_dim * cfg.ssm_state + d_in * d)
    return 2.0 * b * (cfg.n_layers * per_layer + d * cfg.padded_vocab)


def phase_original(cfg, seed: int, kind: str):
    import jax
    import numpy as np

    step = decode_step_fn(cfg)
    params, cache, batch, pos = decode_inputs(cfg, seed)
    jax.block_until_ready((params, cache))
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, batch, pos).compile()
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(TIMED_STEPS):
        t0 = time.perf_counter()
        logits, cache = compiled(params, cache, batch, pos)
        jax.block_until_ready((logits, cache))
        times.append(time.perf_counter() - t0)
    out = np.asarray(logits, np.float32)
    check(out.shape == (BATCH, cfg.padded_vocab),
          f"logits shape {out.shape}")
    check(bool(np.isfinite(out).all()), "non-finite logits")
    check(all(bool(np.isfinite(np.asarray(x, np.float32)).all())
              for x in jax.tree.leaves(cache)), "non-finite decode state")
    step_s = statistics.median(times)
    log(f"[2 original] {cfg.name} decode b={BATCH} bf16 on {kind}: "
        f"compile_s={compile_s:.3f} step_ms_median={step_s * 1e3:.3f} "
        f"steps={TIMED_STEPS}")
    return step_s


def phase_reference(cfg, seed: int) -> None:
    """The reduced config's decode step on the chip agrees with the CPU."""
    import jax
    import numpy as np

    step = jax.jit(decode_step_fn(cfg))
    args = decode_inputs(cfg, seed)
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        chip, _ = step(*args)
        host, _ = step(*jax.device_put(args, cpu))
    chip = np.asarray(chip, np.float64)
    host = np.asarray(host, np.float64)
    err = float(np.max(np.abs(chip - host)) / max(np.max(np.abs(host)), 1e-30))
    log(f"[2 reference] {cfg.name} decode chip vs cpu: rel_err={err:.3e} "
        f"(limit {REF_TOL})")
    check(err <= REF_TOL, f"chip decode disagrees with cpu: {err:.3e}")


def phase_synthesize(cfg):
    import jax
    import jax.numpy as jnp
    from repro.core.synthesize import synthesize
    from repro.models.model import abstract_cache, init_abstract

    step = decode_step_fn(cfg)
    args = (init_abstract(cfg), abstract_cache(cfg, BATCH, 1),
            {"tokens": jax.ShapeDtypeStruct((BATCH, 1), jnp.int32)},
            jax.ShapeDtypeStruct((), jnp.int32))
    t0 = time.perf_counter()
    res = synthesize(step, *args, axis_sizes={})
    synth_s = time.perf_counter() - t0
    stats = {k: res.stats[k] for k in ("n_ranks", "n_events",
                                       "n_unique_terminals", "n_rules",
                                       "compression_ratio", "solver",
                                       "mean_fit_rel_err")}
    mxu = float(res.store.compute_totals()[0][0])
    want = decode_mxu_flops(cfg, BATCH)
    log(f"[3 synthesize] wall_s={synth_s:.3f} stats={json.dumps(stats)}")
    log(f"[3 synthesize] walker mxu_flops={mxu:.6e} dense-matmul "
        f"count={want:.6e}")
    check(abs(mxu - want) <= 1e-9 * want, "walker MXU flops differ from "
          "the step's dense matmul count")
    return res


def phase_replay(res, orig_step_s: float, kind: str) -> None:
    import jax
    import numpy as np

    t0 = time.perf_counter()
    out = res.proxy.run_all()
    first_s = time.perf_counter() - t0
    check(all(bool(np.isfinite(np.asarray(x, np.float32)).all())
              for st in out.values() for x in jax.tree.leaves(st)),
          "non-finite proxy state")
    proxy_s = res.proxy.time_all(iters=PROXY_ITERS)
    combos = {int(g): list(x) + [u] for g, (x, u) in res.proxy.combos.items()}
    log(f"[4 replay] proxy combos (block counts + unroll)={json.dumps(combos)}")
    log(f"[4 replay] on {kind}: proxy compile+first_run_s={first_s:.3f} "
        f"proxy_step_ms={proxy_s * 1e3:.3f} original_step_ms="
        f"{orig_step_s * 1e3:.3f} proxy/original={proxy_s / orig_step_s:.4f}")
    fid = res.fidelity(sample_ranks=None)
    log(f"[4 replay] walker delta_mean={fid.mean:.6f} "
        f"comm_lossless={fid.comm_lossless} (informational)")
    check(fid.comm_lossless, "decode proxy is not comm-lossless")


def phase_corpus() -> None:
    from repro.core.synthesize import synthesize_corpus

    baseline = json.loads((ROOT / "artifacts" / "fidelity_baseline.json")
                          .read_text())
    t0 = time.perf_counter()
    corp = synthesize_corpus(**baseline["measure_kwargs"])
    wall_s = time.perf_counter() - t0
    log(f"[5 corpus] measure_kwargs={baseline['measure_kwargs']} "
        f"wall_s={wall_s:.3f} n_solver_calls={corp.stats['n_solver_calls']} "
        f"n_compute_terminals={corp.stats['n_compute_terminals']}")
    check(corp.stats["n_solver_calls"] == 1, "corpus fit took more than one "
          "solver dispatch")
    bad = []
    for sname, want in baseline["scenarios"].items():
        fid = corp.results[sname].fidelity(sample_ranks=None)
        band = want.get("expected_band")
        if band is not None:
            lo, hi = band
        else:
            lo, hi = 0.0, want["mean_delta"] + baseline["tolerance"]
        ok = fid.comm_lossless and lo <= fid.mean <= hi
        log(f"[5 corpus] {sname}: delta_mean={fid.mean:.6f} baseline="
            f"{want['mean_delta']:.6f} accepted=[{lo:.4f}, {hi:.4f}] "
            f"comm_lossless={fid.comm_lossless} {'ok' if ok else 'FAIL'}")
        if not ok:
            bad.append(sname)
    check(not bad, f"corpus fidelity outside the baseline: {bad}")


def comm_signature(events) -> list[tuple]:
    return [(e.kind, tuple(e.shape), str(e.dtype), tuple(e.axes))
            for e in events]


def phase_mesh(kind: str) -> None:
    """Four chips: stencil step, mesh-sharded vs LocalSim replay."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.common import stencil_program
    from repro.core.events import is_comm
    from repro.core.synthesize import synthesize
    from repro.launch.mesh import make_replay_mesh

    n = jax.device_count()
    f, args, axes = stencil_program(n)
    mesh = make_replay_mesh(axes)
    args = jax.device_put(args, (NamedSharding(mesh, P(None, "x")),
                                 NamedSharding(mesh, P())))
    step = jax.jit(f)
    t0 = time.perf_counter()
    jax.block_until_ready(step(*args))
    orig_compile_s = time.perf_counter() - t0
    times = []
    for _ in range(TIMED_STEPS):
        t0 = time.perf_counter()
        jax.block_until_ready(step(*args))
        times.append(time.perf_counter() - t0)
    orig_s = statistics.median(times)

    res = synthesize(f, *args, axis_sizes=axes)
    log(f"[mesh] stencil n={n} stats={json.dumps(res.stats)}")
    plan = res.proxy.mesh_sweep_plan(mesh)
    for pl in plan:
        log(f"[mesh] plan group ranks={list(pl.ranks)} "
            f"device_ids={list(pl.device_ids)} axis_sizes={dict(pl.axis_sizes)}")

    t0 = time.perf_counter()
    out_mesh = res.proxy.run_all(mesh=mesh)
    mesh_first_s = time.perf_counter() - t0
    mesh_devs = list(np.asarray(mesh.devices).flat)
    for pl in plan:
        want = {mesh_devs[i].id for i in pl.device_ids}
        for r in pl.ranks:
            for leaf in jax.tree.leaves(out_mesh[r]):
                got = {d.id for d in leaf.devices()}
                check(got == want, f"rank {r} output on devices {sorted(got)}, "
                      f"plan gave {sorted(want)}")
    log(f"[mesh] group outputs sit on their planned devices "
        f"({len(plan)} groups)")

    # per rank: the collectives the mesh executables run on the chips, the
    # proxy program's comm sequence (what LocalSim replays: the program
    # tables' expansion) and the original trace must all agree
    mesh_events = res.proxy.mesh_comm_events(mesh)
    table = res.merged.table
    for r, evs in enumerate(res.rank_traces):
        orig = comm_signature(e for e in evs if is_comm(e))
        program = comm_signature(
            table[i] for i in res.proxy.module.expand_rank_ids(r)
            if is_comm(table[i]))
        on_mesh = comm_signature(mesh_events[r])
        check(program == orig, f"rank {r}: proxy comm sequence differs from "
              "the original trace")
        check(on_mesh == orig, f"rank {r}: collectives run on the mesh differ "
              "from the original trace")
    log(f"[mesh] per-rank collective sequences identical: mesh executables, "
        f"proxy program (LocalSim) and original ({n} ranks, "
        f"{len(mesh_events[0])} collectives on rank 0)")

    fid_mesh = res.fidelity(sample_ranks=None, mesh=mesh)
    fid_local = res.fidelity(sample_ranks=None)
    check(bool(np.array_equal(fid_mesh.delta, fid_local.delta)),
          "mesh and LocalSim delta differ")
    check(fid_mesh.comm_lossless and fid_local.comm_lossless,
          "stencil proxy is not comm-lossless")
    check(fid_mesh.mesh_checked, "mesh replay produced non-finite state")
    log(f"[mesh] delta_mean mesh={fid_mesh.mean:.6f} "
        f"local={fid_local.mean:.6f} comm_lossless=True")

    mesh_s = res.proxy.time_all(mesh=mesh, iters=PROXY_ITERS)
    local_s = res.proxy.time_all(iters=PROXY_ITERS)
    log(f"[mesh] on {n}x {kind}: original compile_s={orig_compile_s:.3f} "
        f"step_ms={orig_s * 1e3:.3f}; proxy mesh compile+first_run_s="
        f"{mesh_first_s:.3f} mesh_sweep_ms={mesh_s * 1e3:.3f} "
        f"localsim_sweep_ms={local_s * 1e3:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases 1-5; 4: mesh-sharded replay only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    setup_paths()
    from repro.launch.compile_cache import enable_compile_cache
    device = phase_device(args.chips)
    log(f"[1 device] compile cache: {enable_compile_cache(ROOT)}")

    if args.chips == 4:
        phase_mesh(device["kind"])
    else:
        from repro.configs.base import smoke
        from repro.configs.registry import get
        cfg = get(ARCH)
        orig_s = phase_original(cfg, args.seed, device["kind"])
        phase_reference(smoke(cfg), args.seed)
        res = phase_synthesize(cfg)
        phase_replay(res, orig_s, device["kind"])
        phase_corpus()

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
