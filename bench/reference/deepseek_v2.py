"""Plain reference of DeepSeek-V2 (the Lite shape), in float32.

Written from the published description (arXiv:2405.04434 and the model's
``config.json`` / ``modeling_deepseek.py``):

* multi-head latent attention without query compression (``q_lora_rank``
  null): ``q = x W_q`` split per head into ``q_nope`` and ``q_pe``;
  ``[c_kv | k_pe] = x W_kv_a`` with ``c_kv`` RMS-normed (``kv_a_layernorm``)
  and ``k_pe`` one rope key shared by every head; ``[k_nope | v] = c_kv
  W_kv_b`` per head — the *unabsorbed* form, keys and values decompressed;
  scores ``(q_nope·k_nope + q_pe·k_pe) · s`` with ``s = (nope + rope)^-1/2 ·
  mscale(factor, mscale_all_dim)²``; causal softmax; ``o_proj`` over the
  heads' values;
* the rope over ``qk_rope_head_dim`` in DeepSeek's pair order (each
  vector de-interleaved from ``(d/2, 2)`` before ``rotate_half``), with
  YaRN's inverse frequencies: the interpolated ``f / factor`` and the
  original ``f`` blended by a linear ramp between the correction dims of
  ``beta_fast`` and ``beta_slow`` at the original context length, and
  cos/sin scaled by ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)``;
* layers before ``first_k_dense_replace`` take a dense SiLU-gated MLP of
  ``intermediate_size``; the rest a mixture of experts: ``p = softmax(x
  W_gate)`` in float32, the top ``num_experts_per_tok`` of ``p`` (greedy),
  renormalised only under ``norm_topk_prob``, times
  ``routed_scaling_factor``; ``y = Σ p_i E_i(x) + S(x)`` with SiLU-gated
  experts ``E_i`` of ``moe_intermediate_size`` and the shared experts
  ``S`` one MLP of ``n_shared_experts · moe_intermediate_size``. The
  experts run as a loop over every expert with a zero gate for the tokens
  it was not chosen by: no capacity, no batching of rows;
* pre-norm residuals, a final RMSNorm, an untied head.

It imports nothing of the program under test and is handed nothing the
program made: :func:`make_params` makes the weights from the run's seed,
in the layout the serving program takes. Three numerics, as in
``llama.py`` (whose quantizers it reuses): ``exact``, ``sc`` (every
projection through the multiplier: ``W_q``, ``W_kv_a``, ``o_proj``, the
dense MLP, each expert, the shared experts, the head) and ``fp8_weights``
(the same projections' weights rounded to float8 e4m3 per output column:
the control, never the reference). The router and the decompression
``c_kv W_kv_b`` stay float32 in all three, and attention's score and value
products are exact float32.

Departures from the published description: ``routed_scaling_factor``
must be 1 and ``moe_layer_freq`` 1, the only values the configurations
here use; weights are random (``assumed`` in the configuration file); under
``sc``, ``c_kv W_kv_b`` is exact: a program that attends in the absorbed
form (the query through ``W_UK``, the weighted latent through ``W_UV``)
never forms that product, and the multiplier, which quantizes its
activation operand per row, cannot give the same numbers on other
operands, so the product is left out of the SC numeric on both sides.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .llama import _fp8_weights, sc_product, seed_key

__all__ = ["Sizes", "make_params", "param_shapes", "logits_at",
           "yarn_mscale", "yarn_correction_range", "yarn_inv_freq"]


class Sizes:
    """The model's sizes, read from a configuration file's ``model`` group
    (Hugging Face key names)."""

    def __init__(self, model: dict, derived: dict):
        self.layers = int(model["num_hidden_layers"])
        self.d = int(model["hidden_size"])
        self.heads = int(model["num_attention_heads"])
        self.kv_heads = int(model["num_key_value_heads"])
        self.head_dim = int(derived["head_dim"])
        self.ff = int(model["intermediate_size"])
        self.vocab = int(model["vocab_size"])
        self.eps = float(model["rms_norm_eps"])
        self.theta = float(model["rope_theta"])
        self.rank = int(model["kv_lora_rank"])
        self.nope = int(model["qk_nope_head_dim"])
        self.rope = int(model["qk_rope_head_dim"])
        self.v = int(model["v_head_dim"])
        self.experts = int(model["n_routed_experts"])
        self.top_k = int(model["num_experts_per_tok"])
        self.moe_ff = int(model["moe_intermediate_size"])
        self.shared_ff = int(model["n_shared_experts"]) * self.moe_ff
        self.dense_layers = int(model["first_k_dense_replace"])
        self.norm_topk = bool(model["norm_topk_prob"])
        rs = model.get("rope_scaling") or {}
        self.yarn = (float(rs["factor"]), int(rs["original_max_position_embeddings"]),
                     float(rs["beta_fast"]), float(rs["beta_slow"]),
                     float(rs["mscale"]), float(rs["mscale_all_dim"])
                     ) if rs.get("type") == "yarn" else None
        checks = {"q_lora_rank": None, "routed_scaling_factor": 1.0,
                  "moe_layer_freq": 1, "scoring_func": "softmax",
                  "topk_method": "greedy", "tie_word_embeddings": False,
                  "hidden_act": "silu"}
        for k, want in checks.items():
            if model.get(k, want) != want:
                raise ValueError(f"this reference implements {k}={want!r}, "
                                 f"not {model[k]!r}")
        if self.head_dim != self.nope + self.rope:
            raise ValueError("head_dim must be qk_nope + qk_rope")

    def key(self) -> tuple:
        return (self.layers, self.d, self.heads, self.rank, self.nope,
                self.rope, self.v, self.ff, self.vocab, self.experts,
                self.top_k, self.moe_ff, self.shared_ff, self.dense_layers,
                self.norm_topk, self.eps, self.theta, self.yarn)


# ------------------------------------------------------------------ weights

def param_shapes(sizes_key: tuple) -> dict:
    """Shapes of the weight tree, in the layout the serving program takes:
    the leading dense layers stacked under ``lead``, the MoE layers stacked
    on a leading layer axis under ``layers`` (one layer per scanned group),
    attention weights with an explicit head axis."""
    (L, d, h, r, nope, rp, vd, f, V, E, _, fe, fs, n0) = sizes_key[:14]

    def layer(n, mlp):
        return {"ln1": (n, d), "ln2": (n, d),
                "attn": {"wq": (n, d, h, nope + rp), "wkv_a": (n, d, r + rp),
                         "kv_norm": (n, r), "wkv_b": (n, r, h, nope + vd),
                         "wo": (n, h, vd, d)},
                **mlp}
    moe = {"moe": {"router": (L - n0, d, E), "w1": (L - n0, E, d, fe),
                   "w3": (L - n0, E, d, fe), "w2": (L - n0, E, fe, d),
                   "shared": {"w1": (L - n0, d, fs), "w3": (L - n0, d, fs),
                              "w2": (L - n0, fs, d)}}}
    dense = {"mlp": {"w1": (n0, d, f), "w3": (n0, d, f), "w2": (n0, f, d)}}
    tree = {"embed": (V, d), "final_norm": (d,), "lm_head": (d, V),
            "layers": (layer(L - n0, moe),)}
    if n0:
        tree["lead"] = layer(n0, dense)
    return tree


def _fan_in(name: str, shape: tuple) -> int:
    leaf = name.rsplit("/", 1)[-1]
    if name == "embed":
        return shape[-1]
    if leaf in ("wq", "wkv_b"):
        return shape[-3]
    if leaf == "wo":
        return shape[-3] * shape[-2]
    return shape[-2]


@functools.partial(jax.jit, static_argnames=("shape", "norm", "fan_in",
                                             "dtype"))
def _leaf(key, shape, norm, fan_in, dtype):
    """One weight: normal with std ``fan_in^-1/2``, or a norm gain
    ``1 + 0.1·N(0, 1)``, drawn one slice of the leading axis at a time so
    no float32 copy of a whole expert stack exists."""
    def draw(k, shp):
        z = jax.random.normal(k, shp, jnp.float32)
        return (1.0 + 0.1 * z if norm else z * fan_in ** -0.5).astype(dtype)
    if len(shape) < 3:
        return draw(key, shape)
    return jax.lax.map(lambda k: draw(k, shape[1:]),
                       jax.random.split(key, shape[0]))


def make_params(sizes: Sizes, seed: int, dtype=jnp.bfloat16):
    """The weight tree for ``seed`` on the default device, in ``dtype``
    (the served type), one jitted draw per leaf."""
    shapes = param_shapes(sizes.key())
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(i, int) for i in x))
    keys = jax.random.split(seed_key(seed), len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        norm = "norm" in name or name.endswith(("ln1", "ln2"))
        out.append(_leaf(k, shape, norm, 1 if norm else _fan_in(name, shape),
                         jnp.dtype(dtype)))
    return jax.tree_util.tree_unflatten(tree, out)


# --------------------------------------------------------------------- YaRN

def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(dim: int, base: float, max_pos: int,
                          beta_fast: float, beta_slow: float):
    def dim_of(rot):
        return (dim * math.log(max_pos / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    return (max(math.floor(dim_of(beta_fast)), 0),
            min(math.ceil(dim_of(beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, base: float, yarn) -> np.ndarray:
    """float32 inverse frequencies of the rope, YaRN-blended when ``yarn``
    (factor, original length, beta_fast, beta_slow, ...) is given."""
    exps = np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)
    f_extra = (1.0 / np.float32(base) ** exps).astype(np.float32)
    if yarn is None:
        return f_extra
    factor, orig, fast, slow = yarn[:4]
    f_inter = (1.0 / (np.float32(factor) * np.float32(base) ** exps)
               ).astype(np.float32)
    low, high = yarn_correction_range(dim, base, orig, fast, slow)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / np.float32(high - low), 0, 1)
    mask = (1.0 - ramp).astype(np.float32)
    return (f_inter * (1 - mask) + f_extra * mask).astype(np.float32)


# ----------------------------------------------------------------- forward

def _projector(numerics: str, bits: int):
    if numerics == "exact":
        return lambda x, w: jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    if numerics == "sc":
        return lambda x, w: sc_product(x, w, bits)
    if numerics == "fp8_weights":
        return lambda x, w: jnp.dot(x, _fp8_weights(w),
                                    precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown numerics {numerics!r}")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, cos, sin):
    """DeepSeek's ``apply_rotary_pos_emb`` on ``x (B, S, ..., d)``: the
    vector viewed as ``(d/2, 2)`` and transposed, then ``x cos +
    rotate_half(x) sin`` with ``cos, sin (S, d)``."""
    d = x.shape[-1]
    x = jnp.swapaxes(x.reshape(*x.shape[:-1], d // 2, 2), -1, -2
                     ).reshape(x.shape)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (d,)
    return x * cos.reshape(shape) + _rotate_half(x) * sin.reshape(shape)


def _ffn(x, w1, w3, w2, dense):
    return dense(jax.nn.silu(dense(x, w1)) * dense(x, w3), w2)


@functools.partial(jax.jit, static_argnames=("sizes_key", "numerics", "bits"))
def _logits_at(params, tokens, rows, sizes_key, numerics, bits):
    (L, d, h, r, nope, rp, vd, f, V, E, k, fe, fs, n0, norm_topk, eps, theta,
     yarn) = sizes_key
    proj = _projector(numerics, bits)
    f32 = jnp.float32
    b, s = tokens.shape
    hi = jax.lax.Precision.HIGHEST

    def dense(x, w):
        w = w.astype(f32)
        return proj(x.reshape(-1, x.shape[-1]), w).reshape(
            *x.shape[:-1], w.shape[-1])

    inv = jnp.asarray(yarn_inv_freq(rp, theta, yarn))
    ang = jnp.arange(s, dtype=f32)[:, None] * inv[None, :]
    emb = jnp.concatenate([ang, ang], axis=-1)                # (S, rope)
    m = (yarn_mscale(yarn[0], yarn[4]) / yarn_mscale(yarn[0], yarn[5])
         if yarn else 1.0)
    cos, sin = jnp.cos(emb) * m, jnp.sin(emb) * m
    scale = (nope + rp) ** -0.5
    if yarn and yarn[5]:
        scale *= yarn_mscale(yarn[0], yarn[5]) ** 2
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def attention(x, p):
        q = dense(x, p["wq"].reshape(d, h * (nope + rp))).reshape(
            b, s, h, nope + rp)
        kv = dense(x, p["wkv_a"])
        c = _rms(kv[..., :r], p["kv_norm"].astype(f32), eps)
        k_pe = _rope(kv[..., r:], cos, sin)                   # (B, S, rope)
        q_pe = _rope(q[..., nope:], cos, sin)
        kvb = jnp.dot(c, p["wkv_b"].reshape(r, h * (nope + vd)).astype(f32),
                      precision=hi).reshape(b, s, h, nope + vd)

        def head(args):      # one head at a time: (B, S, S) scores
            qn, qp, kn, v = args
            sc = (jnp.einsum("bsd,btd->bst", qn, kn, precision=hi)
                  + jnp.einsum("bsd,btd->bst", qp, k_pe, precision=hi))
            sc = jnp.where(causal, sc * scale, -jnp.inf)
            return jnp.einsum("bst,btd->bsd", jax.nn.softmax(sc, axis=-1), v,
                              precision=hi)
        per = lambda a: jnp.moveaxis(a, 2, 0)
        o = jax.lax.map(head, (per(q[..., :nope]), per(q_pe),
                               per(kvb[..., :nope]), per(kvb[..., nope:])))
        o = jnp.moveaxis(o, 0, 2).reshape(b, s, h * vd)
        return dense(o, p["wo"].reshape(h * vd, d))

    def moe(x, p):
        logits = jnp.dot(x.reshape(-1, d), p["router"].astype(f32),
                         precision=hi)
        probs = jax.nn.softmax(logits, axis=-1)               # (T, E)
        top, idx = jax.lax.top_k(probs, k)
        if norm_topk:
            top = top / top.sum(-1, keepdims=True)
        gates = (jax.nn.one_hot(idx, E, dtype=f32) * top[..., None]).sum(1)
        xt = x.reshape(-1, d)

        def expert(acc, e):
            y = _ffn(xt, p["w1"][e], p["w3"][e], p["w2"][e], dense)
            return acc + gates[:, e, None] * y, None
        y, _ = jax.lax.scan(expert, jnp.zeros_like(xt), jnp.arange(E))
        sh = p["shared"]
        return y.reshape(b, s, d) + _ffn(x, sh["w1"], sh["w3"], sh["w2"],
                                         dense)

    def layer(x, lp, ffn):
        x = x + attention(_rms(x, lp["ln1"].astype(f32), eps), lp["attn"])
        return x + ffn(_rms(x, lp["ln2"].astype(f32), eps), lp)

    x = params["embed"][tokens].astype(f32)                   # (B, S, d)
    for i in range(n0):
        lp = jax.tree.map(lambda a: a[i], params["lead"])
        x = layer(x, lp, lambda y, lp: _ffn(y, lp["mlp"]["w1"],
                                            lp["mlp"]["w3"], lp["mlp"]["w2"],
                                            dense))
    x, _ = jax.lax.scan(
        lambda x, lp: (layer(x, lp, lambda y, lp: moe(y, lp["moe"])), None),
        x, params["layers"][0])
    x = _rms(x, params["final_norm"].astype(f32), eps)
    sel = jnp.take_along_axis(x, rows[:, :, None], axis=1)    # (B, R, d)
    return dense(sel, params["lm_head"])                      # (B, R, V)


def logits_at(params, tokens, rows, sizes: Sizes, numerics: str = "exact",
              bits: int = 8) -> jax.Array:
    """Next-token logits ``(B, R, V)`` at positions ``rows: (B, R)`` of the
    sequences ``tokens: (B, S)``, from a full causal forward. Rows past a
    sequence's real length read padding and are the caller's to ignore."""
    return _logits_at(params, jnp.asarray(tokens, jnp.int32),
                      jnp.asarray(rows, jnp.int32), sizes.key(), numerics,
                      int(bits))
