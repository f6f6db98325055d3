"""Plain float32 reference of a dense decoder-only transformer (Llama /
Qwen2 layout), its weights from a seed, AdamW, and the counts of
operations and bytes that the benchmark's shares of peak divide by.

Written from the published description (Hugging Face ``LlamaModel`` /
``Qwen2Model``): RMSNorm before attention and MLP, rotary embeddings on
the first and second halves of each head (``rotate_half``), grouped-query
attention with key/value head ``h // (H / KVH)`` for query head ``h``,
SwiGLU MLP, final RMSNorm, tied or untied output projection.  Qwen2 adds
a bias to the query, key and value projections.  No kernels, caches or
batching tricks; every matrix product runs at ``Precision.HIGHEST``.

``quant="fp8"`` rounds both operands of every matrix product to
float8_e4m3fn first: the control that a benchmark limit has to reject.

``PROGRAM_PATHS`` and ``program_overrides`` name the same weights and
sizes as the program under test calls them; they are tables of names,
and the reference computes nothing with the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    return {"D": D, "F": c["intermediate_size"], "L": c["num_hidden_layers"],
            "H": H, "KVH": c["num_key_value_heads"], "hd": D // H,
            "V": c["vocab_size"]}


def qkv_bias(c: dict) -> bool:
    # Qwen2 always carries the bias; Llama states it in ``attention_bias``
    return c.get("attention_bias", c.get("model_type") == "qwen2")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def weight_specs(c: dict) -> dict:
    """name -> (shape, init, fan_in).  Layer weights are stacked on a
    leading layer axis."""
    d = dims(c)
    D, F, L, H, KVH, hd, V = (d[k] for k in ("D", "F", "L", "H", "KVH",
                                             "hd", "V"))
    specs = {
        "embed": ((V, D), "embed", None),
        "final_norm": ((D,), "scale", None),
        "ln1": ((L, D), "scale", None),
        "ln2": ((L, D), "scale", None),
        "wq": ((L, D, H, hd), "normal", D),
        "wk": ((L, D, KVH, hd), "normal", D),
        "wv": ((L, D, KVH, hd), "normal", D),
        "wo": ((L, H, hd, D), "normal", H * hd),
        "wg": ((L, D, F), "normal", D),
        "wu": ((L, D, F), "normal", D),
        "wd": ((L, F, D), "normal", F),
    }
    if qkv_bias(c):
        specs.update(bq=((L, H, hd), "bias", None),
                     bk=((L, KVH, hd), "bias", None),
                     bv=((L, KVH, hd), "bias", None))
    if not c["tie_word_embeddings"]:
        specs["lm_head"] = ((D, V), "normal", D)
    return specs


def init_weights(c: dict, key) -> dict:
    """float32 weights from ``key``; leaf ``i`` (in sorted name order)
    draws from ``fold_in(key, i)``.  Norm scales and biases are drawn
    too, so that no part of a layer is the identity."""
    out = {}
    for i, (name, (shape, kind, fan_in)) in enumerate(
            sorted(weight_specs(c).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if kind == "embed":
            out[name] = 0.02 * z
        elif kind == "scale":
            out[name] = 1.0 + 0.1 * z
        elif kind == "bias":
            out[name] = 0.2 * z
        else:
            out[name] = z / math.sqrt(fan_in)
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _q(x, quant):
    if quant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, _q(a, quant), _q(b, quant), precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B, S, heads, hd] at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _layer(c, x, w, quant):
    d = dims(c)
    eps = c["rms_norm_eps"]
    h = _rms(x, w["ln1"], eps)
    q = _mm("bsd,dhk->bshk", h, w["wq"], quant)
    k = _mm("bsd,dhk->bshk", h, w["wk"], quant)
    v = _mm("bsd,dhk->bshk", h, w["wv"], quant)
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    rep = d["H"] // d["KVH"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, quant) / math.sqrt(d["hd"])
    S = x.shape[1]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bhqk,bkhd->bqhd", p, v, quant)
    x = x + _mm("bshk,hkd->bsd", o, w["wo"], quant)
    h = _rms(x, w["ln2"], eps)
    g = _mm("bsd,df->bsf", h, w["wg"], quant)
    u = _mm("bsd,df->bsf", h, w["wu"], quant)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, w["wd"], quant)


LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
              "wg", "wu", "wd")


def logits(c: dict, w: dict, tokens, quant: str | None = None):
    """tokens [B, S] int -> logits [B, S, V] float32.  Layers run one at a
    time under ``lax.scan`` and each is recomputed in the backward pass,
    so that a full-size model fits beside its gradients."""
    x = w["embed"][tokens]
    stacked = {k: w[k] for k in LAYER_KEYS if k in w}

    @jax.checkpoint
    def body(x, lw):
        return _layer(c, x, lw, quant), None

    x, _ = jax.lax.scan(body, x, stacked)
    x = _rms(x, w["final_norm"], c["rms_norm_eps"])
    if c["tie_word_embeddings"]:
        return _mm("bsd,vd->bsv", x, w["embed"], quant)
    return _mm("bsd,dv->bsv", x, w["lm_head"], quant)


def loss(c: dict, w: dict, tokens, labels, quant: str | None = None):
    """Mean next-token cross-entropy."""
    lg = logits(c, w, tokens, quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def loss_and_grad(c: dict, w: dict, tokens, labels, rows: int,
                  quant: str | None = None):
    """Mean loss and its gradient over [B, S], ``rows`` rows at a time;
    only rows that the caller passes count (a half batch is a half
    batch's mean)."""
    B = tokens.shape[0]
    blocks = B // rows
    tb = tokens.reshape(blocks, rows, -1)
    lb = labels.reshape(blocks, rows, -1)
    vg = jax.value_and_grad(lambda w_, t, l: loss(c, w_, t, l, quant))

    def body(carry, tl):
        ls, gs = carry
        l_, g_ = vg(w, *tl)
        return (ls + l_, jax.tree.map(jnp.add, gs, g_)), None

    zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, w))
    (ls, gs), _ = jax.lax.scan(body, zero, (tb, lb))
    return ls / blocks, jax.tree.map(lambda g: g / blocks, gs)


# ---------------------------------------------------------------------------
# AdamW (decoupled weight decay, global-norm clipping, linear warm-up then
# cosine decay to min_lr_ratio * lr)
# ---------------------------------------------------------------------------

def lr_at(o: dict, step):
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(o["warmup_steps"], 1), 1.0)
    t = jnp.clip((step - o["warmup_steps"])
                 / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * t))
    return o["lr"] * warm * (o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * cos)


def adamw_step(o: dict, step, w, m, v, g):
    """One AdamW update (``step`` counts from 1).  Returns (w, m, v,
    clipped gradient)."""
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, o["clip_norm"]
                                               / (gnorm + 1e-9)), g)
    b1, b2, lr = o["b1"], o["b2"], lr_at(o, step)
    step = jnp.asarray(step, jnp.float32)
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)

    def upd(p, m_, v_):
        mhat, vhat = m_ / (1 - b1 ** step), v_ / (1 - b2 ** step)
        return p - lr * (mhat / (jnp.sqrt(vhat) + o["eps"])
                         + o["weight_decay"] * p)

    return jax.tree.map(upd, w, m, v), m, v, g


# ---------------------------------------------------------------------------
# counts of operations and bytes
# ---------------------------------------------------------------------------

def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d = dims(c)
    attn = d["D"] * (d["H"] + 2 * d["KVH"]) * d["hd"] + d["H"] * d["hd"] * d["D"]
    return attn + 3 * d["D"] * d["F"]


def param_count(c: dict) -> int:
    """Every weight the model holds (tied embeddings counted once)."""
    total = 0
    for shape, _, _ in weight_specs(c).values():
        total += math.prod(shape)
    return total


def forward_flops(c: dict, context: int, unembed: bool = True) -> int:
    """Operations of one token's forward pass at a position that attends
    to ``context`` keys: 2 per multiply-add of every weight it passes,
    and 4 * heads * head_dim per key per layer for scores and values.
    The output projection counts only where its logits are used."""
    d = dims(c)
    f = 2 * d["L"] * layer_matmul_params(c) + 4 * d["L"] * d["H"] * d["hd"] * context
    return f + (2 * d["D"] * d["V"] if unembed else 0)


def train_flops_per_token(c: dict, seq_len: int) -> int:
    """6 N + 12 L d S (forward and backward; attention over the full
    sequence, as the usual model-FLOPs count has it; recomputation not
    counted)."""
    d = dims(c)
    n = d["L"] * layer_matmul_params(c) + d["D"] * d["V"]
    return 6 * n + 12 * d["L"] * d["D"] * seq_len


def kv_bytes_per_position(c: dict, itemsize: int = 2) -> int:
    """Key and value bytes one position holds over all layers."""
    d = dims(c)
    return 2 * d["L"] * d["KVH"] * d["hd"] * itemsize


# ---------------------------------------------------------------------------
# the program's names for this model
# ---------------------------------------------------------------------------

# weight name -> path in the program's parameter tree
PROGRAM_PATHS = {
    "embed": ("embed",), "final_norm": ("final_norm",),
    "lm_head": ("lm_head",),
    "ln1": ("layers", "ln1"), "ln2": ("layers", "ln2"),
    "wq": ("layers", "attn", "wq"), "wk": ("layers", "attn", "wk"),
    "wv": ("layers", "attn", "wv"), "wo": ("layers", "attn", "wo"),
    "bq": ("layers", "attn", "bq"), "bk": ("layers", "attn", "bk"),
    "bv": ("layers", "attn", "bv"),
    "wg": ("layers", "mlp", "wi_gate"), "wu": ("layers", "mlp", "wi_up"),
    "wd": ("layers", "mlp", "wo"),
}


def program_overrides(c: dict) -> dict:
    """The program's model-config fields that configuration ``c`` sets."""
    return dict(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        qkv_bias=qkv_bias(c), tie_embeddings=c["tie_word_embeddings"],
        rope_theta=float(c["rope_theta"]), rms_norm_eps=c["rms_norm_eps"],
        dtype=c["compute_dtype"], param_dtype=c["param_dtype"])
