"""mamba2-2.7b: its weights and decode state from the seed, and its plain
reference.

The benchmark makes the weights and the starting state itself, on the
device from the seed, in one jitted call each.  The programs that run this
configuration (``programs/mamba2_decode.py``) take them from here.

The plain reference is written here in float32 ``jax.numpy`` at
``highest`` matmul precision; it imports nothing of the program.  It
follows the Mamba-2 SSD recurrence with the program's own conventions,
where they depart from the published model (listed under ``assumed`` in
the sizes file): the gated RMS norm normalizes y before the silu(z) gate,
the embedding is scaled by sqrt(d_model), RMS norms use eps 1e-6 and
gains (1 + w), and the head is the tied, padded embedding.  After the
window it runs layer by layer over the whole served sequence
(teacher-forced on the served tokens, from the same starting state) and
reads, at every position, how far the served token's logit lies below
the reference's best.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
POS_BLOCK = 128            # reference sequence padding and logits block
FP8_MAX = 448.0            # float8_e4m3fn
# With tied embeddings and random weights, the token just fed would win
# every argmax if its embedding dominated the residual stream; the layers'
# sum (rms about 1: OUT_GAIN / rms of silu(z)-gated y) must outweigh the
# embedding (rms EMBED_RMS) by far for greedy decode to depend on them.
EMBED_RMS = 0.04
OUT_GAIN = 1.6


def arch(s: dict):
    from repro.configs.base import ArchConfig
    return ArchConfig(name=s["name"], family="ssm", n_layers=s["n_layers"],
                      d_model=s["d_model"], n_heads=1, n_kv_heads=1, d_ff=0,
                      vocab=s["vocab"], layer_pattern=("m",),
                      ssm_state=s["ssm_state"], ssm_head_dim=s["ssm_head_dim"],
                      ssm_groups=s["ssm_groups"], ssm_expand=s["ssm_expand"],
                      ssm_chunk=s["ssm_chunk"], dtype=s["dtype"])


# -- inputs from the seed ------------------------------------------------------


def _leaf_init(path: str, shape, dtype, key, s: dict):
    """One parameter leaf, by its name in the program's parameter tree."""
    dt = jnp.dtype(dtype)
    name = path.rsplit("/", 1)[-1]
    n_layers = s["n_layers"]

    def normal(std):
        if len(shape) >= 3:          # stacked (layers, ...): one slice at a time
            keys = jax.random.split(key, shape[0])
            return jax.lax.map(
                lambda k: (jax.random.normal(k, shape[1:], jnp.float32)
                           * std).astype(dt), keys)
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    if name == "embed":
        e = normal(EMBED_RMS / math.sqrt(s["d_model"]))
        rows = jnp.arange(shape[0])[:, None] < s["vocab"]
        return jnp.where(rows, e, jnp.zeros((), dt))
    if name in ("ln1", "final_norm", "norm_w"):
        return normal(0.1)
    if name == "in_proj":
        return normal(0.02)
    if name == "out_proj":
        d_in = s["ssm_expand"] * s["d_model"]
        return normal(OUT_GAIN / math.sqrt(n_layers * d_in))
    if name == "conv_w":
        return normal(0.3)
    if name == "conv_b":
        return normal(0.1)
    if name == "d_skip":
        return (1.0 + normal(0.1).astype(jnp.float32)).astype(dt)
    if name == "a_log":
        u = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dt)
    if name == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32,
                               math.log(1e-3), math.log(1e-1))
        dtv = jnp.exp(u)
        return (dtv + jnp.log(-jnp.expm1(-dtv))).astype(dt)
    raise KeyError(f"no initializer for parameter {path!r}")


def _tree_init(abstract, seed: int, init):
    leaves, treedef = jax.tree.flatten_with_path(abstract)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for (path, sd), k in zip(leaves, keys):
            name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path)
            out.append(init(name, sd.shape, sd.dtype, k))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(jax.random.key(seed % (1 << 32)))


def make_weights(s: dict, seed: int):
    """The whole parameter tree on the device, in one jitted call."""
    from repro.models.model import init_abstract
    return _tree_init(init_abstract(arch(s)), seed,
                      lambda n, shp, dt, k: _leaf_init(n, shp, dt, k, s))


def make_state(s: dict, batch: int, seed: int):
    """(decode cache, first tokens) for ``batch`` rows, in one jitted call."""
    from repro.models.model import abstract_cache
    cache_abs = abstract_cache(arch(s), batch, 1)

    def init(name, shape, dtype, key):
        std = 0.1 if name.endswith("state") else 1.0
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    cache = _tree_init(cache_abs, seed + 1, init)
    toks = jax.jit(lambda k: jax.random.randint(k, (batch, 1), 0, s["vocab"],
                                                jnp.int32))(
        jax.random.key((seed + 2) % (1 << 32)))
    return cache, toks


# -- plain reference -------------------------------------------------------------


def _quant(a, mode: str):
    """``mode`` "f32": a as is; "fp8": per-tensor scaled float8_e4m3fn."""
    if mode == "f32":
        return a
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, mode):
    return jnp.matmul(_quant(a, mode), _quant(b, mode), precision=HI)


def _rms(x, w, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _layer(lp, x, state, conv, s: dict, mode: str):
    """One Mamba-2 layer over a whole sequence x (B, T, d), float32, from
    the decode state (state (B, h, p, n), conv history (B, W-1, C))."""
    b, t, d = x.shape
    d_in = s["ssm_expand"] * d
    p, n, g = s["ssm_head_dim"], s["ssm_state"], s["ssm_groups"]
    h = d_in // p
    gn = g * n
    f32 = {k: v.astype(jnp.float32) for k, v in lp.items()}
    zx = _mm(_rms(x, f32["ln1"]), f32["in_proj"], mode)
    z = zx[..., :d_in]
    cin = zx[..., d_in:2 * d_in + 2 * gn]
    dt = zx[..., 2 * d_in + 2 * gn:]
    hist = jnp.concatenate([conv.astype(jnp.float32), cin], axis=1)
    w = f32["conv_w"]
    co = sum(hist[:, i:i + t] * w[i] for i in range(w.shape[0])) + f32["conv_b"]
    co = co * jax.nn.sigmoid(co)
    xs = co[..., :d_in].reshape(b, t, h, p)
    bm = jnp.repeat(co[..., d_in:d_in + gn].reshape(b, t, g, n), h // g, axis=2)
    cm = jnp.repeat(co[..., d_in + gn:].reshape(b, t, g, n), h // g, axis=2)
    dtv = jax.nn.softplus(dt + f32["dt_bias"])                  # (b,t,h)
    da = jnp.exp(dtv * -jnp.exp(f32["a_log"]))

    def step(st, inp):
        da_t, dtv_t, x_t, b_t, c_t = inp
        st = st * da_t[..., None, None] + \
            (dtv_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return st, jnp.einsum("bhpn,bhn->bhp", st, c_t, precision=HI)

    tm = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(step, state.astype(jnp.float32),
                        (tm(da), tm(dtv), tm(xs), tm(bm), tm(cm)))
    y = jnp.moveaxis(y, 0, 1) + f32["d_skip"][None, None, :, None] * xs
    y = _rms(y.reshape(b, t, d_in), f32["norm_w"])
    y = y * (z * jax.nn.sigmoid(z))
    return x + _mm(y, f32["out_proj"], mode)


@functools.lru_cache(maxsize=None)
def _layer_jit(sizes: tuple, mode: str):
    s = dict(sizes)
    return jax.jit(lambda lp, x, st, cv: _layer(lp, x, st, cv, s, mode))


def _hidden(s, params, cache, tokens, mode: str):
    """Final normed hidden states (B, T, d) of the reference over ``tokens``
    (B, T), layer by layer."""
    emb = params["embed"]
    x = jnp.take(emb, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    x = x * math.sqrt(s["d_model"])
    unit = params["unit"][0]
    st = cache["unit"][0]
    key = tuple(sorted((k, v) for k, v in s.items()
                       if isinstance(v, (int, float, str))))
    for li in range(s["n_layers"]):
        lp = {"ln1": unit["ln1"][li]}
        lp.update({k: v[li] for k, v in unit["mixer"].items()})
        x = _layer_jit(key, mode)(lp, x, st["state"][li], st["conv"][li])
    return _rms(x, params["final_norm"].astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(4,))
def _gap_block(hr, hc, emb, served, mode):
    """Per position: the reference's best logit minus the reference's logit
    of ``served`` (or, when ``hc`` is given, of the token that the head in
    ``mode`` puts first on ``hc``)."""
    e = emb.astype(jnp.float32)
    lr = jnp.matmul(hr, e.T, precision=HI)
    best = jnp.max(lr, axis=-1)
    if hc is not None:
        served = jnp.argmax(_mm(hc, e.T, mode), axis=-1)
    got = jnp.take_along_axis(lr, served[:, None], axis=-1)[:, 0]
    return best - got


def reference_gaps(s, params, cache, first, served, control: str | None = None):
    """Gap at every served position (B x T) between the reference's best
    logit and its logit of the served token.  With ``control="fp8"`` the
    token compared is the one an fp8 reference puts first instead.

    ``first`` (B, 1) are the tokens fed to the first step and ``served``
    (B, T) the tokens each step returned; step t consumed token t and
    served token t+1."""
    b, t = served.shape
    feed = np.concatenate([first, served[:, :-1]], axis=1)
    pad = -t % POS_BLOCK
    feed = np.pad(feed, ((0, 0), (0, pad)))
    with jax.default_matmul_precision("highest"):
        hr = _hidden(s, params, cache, feed, "f32")[:, :t]
        hc = (_hidden(s, params, cache, feed, control)[:, :t]
              if control else None)
        hr = hr.reshape(b * t, -1)
        hc = hc.reshape(b * t, -1) if control else None
        want = jnp.asarray(served.reshape(-1))
        out = []
        for i in range(0, b * t, POS_BLOCK):
            blk = slice(i, i + POS_BLOCK)
            out.append(np.asarray(_gap_block(
                hr[blk], None if hc is None else hc[blk],
                params["embed"], want[blk], control or "f32")))
    return np.concatenate(out)
