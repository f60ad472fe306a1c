"""Plain reference of a dense GQA decoder served under GLASS block selection.

Written from the published descriptions alone (Mistral-7B / Yi-9B: RMSNorm,
rotary embeddings in the split-halves convention, grouped-query attention
with an optional sliding window, a SiLU-gated FFN, an untied head; GLASS,
arXiv:2508.14302: per-unit importance E|h_j| / ||h|| from the prompt fused
by rank with a global prior, whole 128-unit blocks kept).  It imports
nothing of the system under test.  Every matrix product runs in float32 at
``Precision.HIGHEST``; ``precision="fp8"`` is the control: each product's
operands are rounded to float8 e4m3 with a per-tensor scale first.

Weights follow one layout, which the benchmark hands to the system under
test as well: ``embed (V, d)``, ``layers`` stacked over a leading L axis
(``attn/{wq,wk,wv,wo}``, ``ln1``, ``ln2``, ``ffn/{w_gate,w_up,w_down}``),
``final_norm (d,)``, ``lm_head (d, V)``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
STATS_EPS = 1e-6  # |h| / (||h||_2 + eps), GLASS's token-normalised activation
Q_CHUNK = 512  # queries per attention block (bounds the score tile)


def dims(hf: dict) -> dict:
    """The sizes the reference reads from a configuration's config.json keys."""
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    return {
        "d": d, "f": hf["intermediate_size"], "H": h,
        "K": hf["num_key_value_heads"], "hd": hf.get("head_dim") or d // h,
        "L": hf["num_hidden_layers"], "V": hf["vocab_size"],
        "eps": hf["rms_norm_eps"], "theta": float(hf["rope_theta"]),
        "window": hf.get("sliding_window") or 2**30,
    }


def init_params(hf: dict, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Seeded random weights: N(0, 1/fan_in) matrices, N(0, 0.02^2)
    embedding, unit norms.  Jit it: every leaf is made on the device, one
    layer at a time, so no float32 copy of a whole stack is ever held."""
    g = dims(hf)
    d, f, L, V = g["d"], g["f"], g["L"], g["V"]
    qd, kd = g["H"] * g["hd"], g["K"] * g["hd"]
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def mat(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[-2])).astype(dtype)

    def layer(k):
        ks = jax.random.split(k, 7)
        return {
            "attn": {"wq": mat(ks[0], (d, qd)), "wk": mat(ks[1], (d, kd)),
                     "wv": mat(ks[2], (d, kd)), "wo": mat(ks[3], (qd, d))},
            "ln1": jnp.ones((d,), dtype),
            "ln2": jnp.ones((d,), dtype),
            "ffn": {"w_gate": mat(ks[4], (d, f)), "w_up": mat(ks[5], (d, f)),
                    "w_down": mat(ks[6], (f, d))},
        }

    return {
        "embed": (0.02 * jax.random.normal(k_embed, (V, d), jnp.float32)).astype(dtype),
        "layers": jax.lax.map(layer, jax.random.split(k_layers, L)),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": mat(k_head, (d, V)),
    }


def _round_fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(spec: str, a, b, precision: str):
    """A product in the reference's precision: ``f32`` (exact float32),
    ``fp8`` (the control) or ``bf16`` (one pass, for counts that need no
    exactness, such as which blocks a prompt keeps)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _round_fp8(a), _round_fp8(b)
    if precision == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, n, hd) rotated at positions pos (S,), halves paired."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ranks(v):
    """Rank 1 = smallest; equal values ranked by unit index."""
    order = jnp.argsort(v, stable=True)
    return jnp.zeros(v.shape, jnp.float32).at[order].set(
        jnp.arange(1, v.shape[-1] + 1, dtype=jnp.float32))


def keep_mask(local, prior, glass: dict):
    """(f,) 0/1 mask of the kept units: GLASS score = (1-lam) rank(local) +
    lam rank(prior); blocks of ``block_size`` ranked by mean score; the top
    ceil(k / block_size) blocks kept, k = round(density * f)."""
    f = local.shape[-1]
    bs = glass["block_size"]
    lam = glass["lam"]
    score = (1.0 - lam) * _ranks(local) + lam * _ranks(prior)
    blocks = jnp.mean(score.reshape(f // bs, bs), -1)
    n_keep = -(-max(1, int(round(glass["density"] * f))) // bs)
    top = jnp.argsort(-blocks, stable=True)[:n_keep]
    kept = jnp.zeros((f // bs,), jnp.float32).at[top].set(1.0)
    return jnp.repeat(kept, bs)


def _attend(q, k, v, pos, window, precision):
    """Causal windowed GQA attention; q (S, H, hd), k/v (S, K, hd)."""
    S, H, hd = q.shape
    G = H // k.shape[1]
    kr = jnp.repeat(k, G, axis=1)
    vr = jnp.repeat(v, G, axis=1)

    def block(args):
        qc, pc = args  # (C, H, hd), (C,)
        s = _mm("chd,thd->hct", qc, kr, precision) / math.sqrt(hd)
        ok = (pc[:, None] >= pos[None]) & (pc[:, None] - pos[None] < window)
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("hct,thd->chd", p, vr, precision)

    c = min(Q_CHUNK, S)
    out = jax.lax.map(block, (q.reshape(S // c, c, H, hd), pos.reshape(S // c, c)))
    return out.reshape(S, H * hd)


def _hidden(lp, x, precision):
    g = _mm("sd,df->sf", x, lp["ffn"]["w_gate"], precision)
    u = _mm("sd,df->sf", x, lp["ffn"]["w_up"], precision)
    return jax.nn.silu(g) * u


def _normalised_abs(h):
    return jnp.abs(h) / (jnp.sqrt(jnp.sum(h * h, -1, keepdims=True)) + STATS_EPS)


def _layers(g, params, extra, tokens, ffn_fn, precision):
    """Run the decoder stack over one sequence.  ``extra`` rides the layer
    scan beside the weights (one row per layer); ``ffn_fn(lp, row, h2)``
    returns (FFN output, per-layer output).  Returns (final hidden,
    stacked per-layer outputs)."""
    S = tokens.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)

    def proj(h, w, n):
        return _mm("sd,de->se", h, w, precision).reshape(S, n, g["hd"])

    def body(x, xs):
        lp, row = xs
        a = lp["attn"]
        h = _rms(x, lp["ln1"], g["eps"])
        q = _rope(proj(h, a["wq"], g["H"]), pos, g["theta"])
        k = _rope(proj(h, a["wk"], g["K"]), pos, g["theta"])
        v = proj(h, a["wv"], g["K"])
        x = x + _mm("se,ed->sd", _attend(q, k, v, pos, g["window"], precision), a["wo"], precision)
        y, out = ffn_fn(lp, row, _rms(x, lp["ln2"], g["eps"]))
        return x + y, out

    x, outs = jax.lax.scan(body, x, (params["layers"], extra))
    return _rms(x, params["final_norm"], g["eps"]), outs


@partial(jax.jit, static_argnums=(0, 3))
def _prior_sums(g_items, params, inputs, precision):
    g = dict(g_items)

    def ffn(lp, _, h2):
        h = _hidden(lp, h2, precision)
        return _mm("sf,fd->sd", h, lp["ffn"]["w_down"], precision), jnp.sum(_normalised_abs(h), 0)

    none = jnp.zeros((g["L"], 0))
    return jax.lax.map(lambda t: _layers(g, params, none, t, ffn, precision)[1], inputs)


def prior(hf: dict, params, corpus, bos_id: int, precision: str = "f32"):
    """Global activation prior (L, f): mean of |h|/||h|| over a teacher-forced
    corpus, inputs [BOS, t_0 .. t_{n-2}] per sequence."""
    n, s = corpus.shape
    inputs = jnp.concatenate([jnp.full((n, 1), bos_id, corpus.dtype), corpus[:, :-1]], 1)
    sums = _prior_sums(tuple(sorted(dims(hf).items())), params, inputs, precision)
    return jnp.sum(sums, 0) / float(n * s)  # (n, L, f) summed over the corpus


@partial(jax.jit, static_argnums=(0, 5, 6))
def _served_logits(g_items, params, prior_, tokens, prompt_len, glass_items, precision):
    g, glass = dict(g_items), dict(glass_items)
    S = tokens.shape[0]
    in_prompt = (jnp.arange(S) < prompt_len).astype(jnp.float32)[:, None]

    def ffn(lp, pr, h2):
        h = _hidden(lp, h2, precision)
        local = jnp.sum(_normalised_abs(h) * in_prompt, 0) / prompt_len
        keep = keep_mask(local, pr, glass)
        gate = in_prompt + (1.0 - in_prompt) * keep[None]  # dense over the prompt
        return _mm("sf,fd->sd", h * gate, lp["ffn"]["w_down"], precision), None

    x, _ = _layers(g, params, prior_, tokens, ffn, precision)
    return _mm("sd,dv->sv", x, params["lm_head"], precision)


def served_logits(hf: dict, params, prior_, tokens, prompt_len: int, glass: dict,
                  precision: str = "f32"):
    """Logits (S, V) over one served sequence (prompt, then served tokens,
    zero-padded at the end; S a multiple of 512 or below it): the prompt
    runs the dense FFN and sets the sequence's kept blocks; every later
    position runs only the kept blocks.  Row i scores the token at i + 1."""
    return _served_logits(tuple(sorted(dims(hf).items())), params, prior_, tokens,
                          jnp.int32(prompt_len), tuple(sorted(glass.items())), precision)


@partial(jax.jit, static_argnums=(0, 5, 6))
def _kept_blocks(g_items, params, prior_, tokens, prompt_lens, glass_items, precision):
    g, glass = dict(g_items), dict(glass_items)

    def one(seq_len):
        toks, n = seq_len
        in_prompt = (jnp.arange(toks.shape[0]) < n).astype(jnp.float32)[:, None]

        def ffn(lp, pr, h2):
            h = _hidden(lp, h2, precision)
            local = jnp.sum(_normalised_abs(h) * in_prompt, 0) / n
            keep = keep_mask(local, pr, glass).reshape(-1, glass["block_size"])[:, 0]
            ids = jnp.nonzero(keep, size=-(-max(1, int(round(glass["density"] * g["f"]))) // glass["block_size"]))[0]
            return _mm("sf,fd->sd", h, lp["ffn"]["w_down"], precision), ids

        return _layers(g, params, prior_, toks, ffn, precision)[1]

    return jax.lax.map(one, (tokens, prompt_lens))


def kept_blocks(hf: dict, params, prior_, tokens, prompt_lens, glass: dict,
                precision: str = "f32"):
    """(n, L, n_keep) block ids each prompt keeps (prompts zero-padded at the
    end to one length, ``tokens`` (n, S), true lengths ``prompt_lens``)."""
    return _kept_blocks(tuple(sorted(dims(hf).items())), params, prior_, tokens,
                        jnp.asarray(prompt_lens, jnp.int32), tuple(sorted(glass.items())),
                        precision)
