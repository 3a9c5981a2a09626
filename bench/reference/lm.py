"""Plain float32 reference of the decoder LMs the benchmark trains.

Written from the published descriptions in straightforward ``jax.numpy``:
no kernels, no cache, no chunked attention (layers run in order under one
``lax.scan``, which keeps the compiled program small). It imports
nothing of the program. The parameter tree uses the program's layout (the
interface the program is driven through): ``embed``, ``ln_f``, optional
``head``, and ``stack/body/p0/...`` leaves stacked over layers.

Departures from the published models, kept because the program makes them
(the reference has to compute what the configuration runs):

- RMSNorm gains are stored as offsets: ``x * rsqrt(mean(x^2) + eps) * (1 + w)``.
- RoPE rotates the two halves of each head (not interleaved pairs).
- MiniCPM3: no ``scale_emb`` on the embedding, no ``dim_model_base`` scaling
  of the logits, plain RoPE (no long-rope factors), eps as in the config.
- The loss adds a z-loss of 1e-4 * logsumexp^2 per token to the cross-entropy.

``mode`` selects the arithmetic of every matrix product: ``"f32"`` is float32
at the highest precision; ``"fp8"`` rounds both operands to float8 e4m3 with
one scale per tensor first (the control: the precision below bfloat16).
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DIGITAL_MARKERS = ("embed", "vocab", "lm_head", "pos")


# ---------------------------------------------------------------------------
# parameter layout and seeded weights
# ---------------------------------------------------------------------------


def layer_kinds(m: dict) -> tuple:
    """The program's layer kind for each layer, in order."""
    return ({"gqa": "attn", "mla": "mla"}[m["attention"]],) * m["n_layers"]


def layer_shapes(m: dict) -> dict:
    """Shapes of one layer's leaves (before stacking over layers)."""
    d, ff = m["d_model"], m["d_ff"]
    if m["attention"] == "gqa":
        H, KV, D = m["n_heads"], m["n_kv"], m["head_dim"]
        attn = {"wq": (d, H * D), "wk": (d, KV * D), "wv": (d, KV * D),
                "wo": (H * D, d)}
        if m.get("qkv_bias"):
            attn.update(bq=(H * D,), bk=(KV * D,), bv=(KV * D,))
    else:
        H = m["n_heads"]
        nope, rope, v = m["qk_nope"], m["qk_rope"], m["v_head_dim"]
        ql, kvl = m["q_lora"], m["kv_lora"]
        attn = {"wdq": (d, ql), "qln": (ql,), "wuq": (ql, H * (nope + rope)),
                "wdkv": (d, kvl), "kvln": (kvl,), "wuk": (kvl, H * nope),
                "wuv": (kvl, H * v), "wkr": (d, rope), "wo": (H * v, d)}
    return {"ln1": (d,), "attn": attn, "ln2": (d,),
            "mlp": {"wi": (d, ff), "wg": (d, ff), "wo": (ff, d)}}


def param_shapes(m: dict) -> dict:
    L = m["n_layers"]
    body = jax.tree.map(lambda s: (L,) + s, layer_shapes(m),
                        is_leaf=lambda x: isinstance(x, tuple))
    out = {"embed": (m["vocab"], m["d_model"]), "ln_f": (m["d_model"],),
           "stack": {"prefix": {}, "body": {"p0": body}, "tail": {}}}
    if not m["tied_embeddings"]:
        out["head"] = (m["d_model"], m["vocab"])
    return out


def leaf_paths(tree, is_leaf=None) -> dict:
    """{"a/b/c": leaf} of a nested dict."""
    flat = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]
    return {"/".join(str(k.key) for k in kp): v for kp, v in flat}


def is_gain_or_bias(path: str) -> bool:
    name = path.rsplit("/", 1)[-1]
    return name.startswith("ln") or name.endswith("ln") or name in (
        "bq", "bk", "bv")


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    key = jax.random.key(np.uint32(seed % 2**32))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def init_params(m: dict, key, dtype=jnp.bfloat16) -> dict:
    """Seeded weights in the served type: every matrix N(0, init_std^2),
    norm gains and biases zero. One key per leaf, folded from ``key``
    (``seed_key``) and a CRC of the leaf's path, so a leaf's values do not
    depend on the others. Jit it with the key as an argument to make the
    whole tree on the device in one call that every seed shares."""
    shapes = param_shapes(m)
    std = m["init_std"]

    def make(path, shape):
        if is_gain_or_bias(path):
            return jnp.zeros(shape, dtype)
        k = jax.random.fold_in(key, np.uint32(zlib.crc32(path.encode())))
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    paths = leaf_paths(shapes, is_leaf=lambda x: isinstance(x, tuple))
    flat = {p: make(p, s) for p, s in paths.items()}
    return unflatten(flat)


def unflatten(flat: dict) -> dict:
    out: dict = {}
    for p, v in flat.items():
        node = out
        parts = p.split("/")
        for k in parts[:-1]:
            node = node.setdefault(k, {})
        node[parts[-1]] = v
    out.setdefault("stack", {}).setdefault("prefix", {})
    out["stack"].setdefault("tail", {})
    return out


def is_digital(path: str, ndim: int) -> bool:
    """Leaves the configuration keeps on the digital optimizer: embeddings,
    vocabulary heads and positional tables by name, and every leaf of
    rank < 2."""
    low = path.lower()
    return ndim < 2 or any(s in low for s in DIGITAL_MARKERS)


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ein8(spec, a, b):
    return _ein(spec, _q8(a), _q8(b))


def _ein8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return _ein(spec, qa, qb), (qa, qb)


def _ein8_bwd(spec, res, g):
    # the backward products take float8 operands too, the cotangent
    # scaled per tensor like every other operand
    return jax.vjp(functools.partial(_ein, spec), *res)[1](_q8(g))


_ein8.defvjp(_ein8_fwd, _ein8_bwd)


def mm(a, b, mode: str, spec: str | None = None):
    if spec is None:
        spec = "...k,kn->...n"
    return _ein8(spec, a, b) if mode == "fp8" else _ein(spec, a, b)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rope(x, pos, base):
    """x (B,S,H,D); rotate the two halves of D by pos * base^(-i/(D/2))."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, :, None, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v, mode):
    """Causal softmax attention; q (B,S,H,Dk), k (B,S,H,Dk), v (B,S,H,Dv)."""
    S = q.shape[1]
    s = mm(q, k, mode, "bqhd,bkhd->bhqk") / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    return mm(p, v, mode, "bhqk,bkhd->bqhd")


def gqa(p, h, m, pos, mode):
    B, S, _ = h.shape
    H, KV, D = m["n_heads"], m["n_kv"], m["head_dim"]
    q, k, v = mm(h, p["wq"], mode), mm(h, p["wk"], mode), mm(h, p["wv"], mode)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q.reshape(B, S, H, D), pos, m["rope_base"])
    k = rope(k.reshape(B, S, KV, D), pos, m["rope_base"])
    v = v.reshape(B, S, KV, D)
    # query head h reads key/value head h // (H / KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    return mm(attention(q, k, v, mode).reshape(B, S, H * D), p["wo"], mode)


def mla(p, h, m, pos, mode):
    B, S, _ = h.shape
    H, nope, r, vd = m["n_heads"], m["qk_nope"], m["qk_rope"], m["v_head_dim"]
    eps = m["norm_eps"]
    ql = rms(mm(h, p["wdq"], mode), p["qln"], eps)
    q = mm(ql, p["wuq"], mode).reshape(B, S, H, nope + r)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, m["rope_base"])], -1)
    ckv = rms(mm(h, p["wdkv"], mode), p["kvln"], eps)
    k_pe = rope(mm(h, p["wkr"], mode)[:, :, None, :], pos, m["rope_base"])
    k_nope = mm(ckv, p["wuk"], mode).reshape(B, S, H, nope)
    v = mm(ckv, p["wuv"], mode).reshape(B, S, H, vd)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, H, r))], -1)
    return mm(attention(q, k, v, mode).reshape(B, S, H * vd), p["wo"], mode)


def mlp(p, h, mode):
    return mm(jax.nn.silu(mm(h, p["wi"], mode)) * mm(h, p["wg"], mode),
              p["wo"], mode)


def forward(params, tokens, m, mode="f32", remat=False):
    """Logits (B,S,V) in float32 for ``tokens`` (B,S)."""
    x = params["embed"][tokens]
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None], tokens.shape)
    mix = gqa if m["attention"] == "gqa" else mla
    rs, eps = m.get("residual_scale", 1.0), m["norm_eps"]

    def layer(x, lp):
        x = x + rs * mix(lp["attn"], rms(x, lp["ln1"], eps), m, pos, mode)
        return x + rs * mlp(lp["mlp"], rms(x, lp["ln2"], eps), mode)

    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp), None), x,
                        params["stack"]["body"]["p0"])
    x = rms(x, params["ln_f"], eps)
    head = params["embed"].T if m["tied_embeddings"] else params["head"]
    return mm(x, head, mode)


def loss_sum(params, tokens, labels, m, mode="f32", remat=False):
    """Sum over tokens of cross-entropy plus z-loss (1e-4 * lse^2)."""
    logits = forward(params, tokens, m, mode, remat)
    lse = jax.scipy.special.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - ll + 1e-4 * lse * lse)
