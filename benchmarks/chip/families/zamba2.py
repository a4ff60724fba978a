"""Zamba2 hybrid language model (arXiv:2411.15242; HF ``Zamba2ForCausalLM``):
the plain reference, the seeded weights, operation and byte counts, and the
map onto the program's layout.

Reference semantics, float32, matmuls at HIGHEST.  With x0 the token
embeddings, for each layer l (the j-th of ``hybrid_layer_ids`` is preceded
by shared block b = j mod ``num_mem_blocks``):
  u = RMSNorm(concat[x, x0]); q, k, v = u·Wq, u·Wk, u·Wv (heads of hd);
  RoPE (rotate-half) on q and k; a = causal softmax(q·kᵀ·(hd/2)^-½)·v·Wo;
  m = RMSNorm(a); [g, up] = m·W_gu + (m·A_j)·B_j (rank-r LoRA of application j);
  T = ((gelu_erf(g) * up)·W_down)·Lin_j         (T = 0 before other layers)
  h = RMSNorm(x + T); [z, x', B, C, dt] = h·W_in
  [x', B, C] = silu(causal depthwise conv over time + bias)
  dt = max(softplus(dt + dt_bias), time_step_min); A = −exp(A_log)
  state_t = state_{t−1}·exp(dt_t·A) + dt_t·x'_t ⊗ B_t   (head h reads group
                                                         h // (heads / groups))
  y_t = state_t·C_t + D·x'_t
  x += W_out·(RMSNorm per group(y ⊙ silu(z)))
then a final RMSNorm and logits = x·Embᵀ (tied).  The recurrence runs token
by token and attention in blocks of query rows (full float32 scores of every
row would not fit), so nothing is shared with the program's chunked scan or
fused kernel.  HF's torch path clamps dt at ``time_step_min`` (its CUDA path
does not); the reference follows the torch path.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
#: query rows per block of the reference's attention
Q_ROWS = 512


def sizes(c: Dict[str, Any]) -> Dict[str, Any]:
    D, G, N = c["hidden_size"], c["mamba_ngroups"], c["mamba_d_state"]
    Di = c["mamba_expand"] * D
    ids = list(c["hybrid_layer_ids"])
    if [i for i, t in enumerate(c["layers_block_type"]) if t == "hybrid"] != ids \
            or len(c["layers_block_type"]) != c["num_hidden_layers"]:
        raise ValueError("layers_block_type and hybrid_layer_ids disagree")
    return {"L": c["num_hidden_layers"], "D": D, "Di": Di, "P": c["mamba_headdim"],
            "N": N, "H": c["n_mamba_heads"], "G": G, "K": c["mamba_d_conv"],
            "Q": c["chunk_size"], "V": c["vocab_size"], "conv_dim": Di + 2 * G * N,
            "nh": c["num_attention_heads"], "nkv": c["num_key_value_heads"],
            "hd": c["attention_head_dim"], "AD": c["attention_hidden_size"],
            "F": c["intermediate_size"], "r": c["adapter_rank"],
            "NB": c["num_mem_blocks"], "ids": ids, "A": len(ids),
            "eps": c["rms_norm_eps"], "theta": c["rope_theta"],
            "dt_min": c["time_step_min"], "dt_max": c["time_step_max"],
            "dt_floor": c["time_step_floor"], "std": c["initializer_range"]}


# ------------------------------------------------------------------ counts
def _mamba_matmul(s) -> int:
    D, Di, N, H, G = s["D"], s["Di"], s["N"], s["H"], s["G"]
    return D * (2 * Di + 2 * G * N + H) + Di * D


def _block_matmul(s) -> int:
    """Weights of one shared block that multiply activations."""
    return (s["AD"] * (s["nh"] + 2 * s["nkv"]) * s["hd"] + s["nh"] * s["hd"] * s["D"]
            + 3 * s["D"] * s["F"])


def _app_matmul(s) -> int:
    """The per-application LoRA (D -> r -> 2F) and output linear (D x D)."""
    return s["r"] * (s["D"] + 2 * s["F"]) + s["D"] * s["D"]


def _mamba_layer_bytes(s) -> int:
    bf16 = _mamba_matmul(s) + (s["K"] + 1) * s["conv_dim"] + s["H"] + s["Di"] + s["D"]
    return 2 * bf16 + 4 * 2 * s["H"]            # A_log and D float32


def _block_bytes(s) -> int:
    return 2 * (_block_matmul(s) + s["AD"] + s["D"])


def state_bytes(c, batch: int) -> int:
    """The SSM state (float32) and the conv tail (bf16) of ``batch`` rows."""
    s = sizes(c)
    return batch * s["L"] * (s["H"] * s["P"] * s["N"] * 4
                             + (s["K"] - 1) * s["conv_dim"] * 2)


def weight_bytes(c) -> int:
    """Bytes of the weights a forward pass reads, as stored: the Mamba2
    layers once, a shared block and its application's adapter and linear
    once per application, the tied embedding once."""
    s = sizes(c)
    return (s["L"] * _mamba_layer_bytes(s)
            + s["A"] * (_block_bytes(s) + 2 * _app_matmul(s))
            + 2 * (s["V"] * s["D"] + s["D"]))


def kv_bytes(c, batch: int, length: int) -> int:
    """Keys and values of every application over ``length`` positions."""
    s = sizes(c)
    return s["A"] * 2 * batch * length * s["nkv"] * s["hd"] * 2


def _per_token_flops(s, keys: float) -> float:
    """One token through every layer, each query over ``keys`` keys."""
    mamba = s["L"] * (2 * _mamba_matmul(s) + 2 * s["K"] * s["conv_dim"])
    apps = s["A"] * (2 * (_block_matmul(s) + _app_matmul(s))
                     + 4 * s["nh"] * s["hd"] * keys)
    return mamba + apps


def prefill_cost(c, batch: int, prompt: int):
    """(operations, HBM bytes) a prefill of ``batch`` prompts needs: the
    projections and conv over every token, the chunked scan (causal half of
    each chunk's quadratic form, chunk states and their read-out), causal
    attention (each query over (prompt + 1) / 2 keys on average), logits at
    the last position; weights read, keys, values and states written."""
    s = sizes(c)
    H, P, N, Q, G = s["H"], s["P"], s["N"], s["Q"], s["G"]
    ssd = s["L"] * (Q * N * G + Q * H * P + 4 * H * P * N)
    flops = batch * prompt * (_per_token_flops(s, (prompt + 1) / 2) + ssd) \
        + 2 * batch * s["V"] * s["D"]
    return float(flops), float(weight_bytes(c) + state_bytes(c, batch)
                               + kv_bytes(c, batch, prompt))


def decode_cost(c, batch: int, cache_len: int):
    """(operations, HBM bytes) of one decode step of ``batch`` rows whose
    queries see ``cache_len`` positions: every weight read (a shared block
    once per application), the keys and values over ``cache_len`` read, the
    states read and written."""
    s = sizes(c)
    per_row = (_per_token_flops(s, cache_len)
               + s["L"] * 4 * s["H"] * s["P"] * s["N"] + 2 * s["V"] * s["D"])
    return (float(batch * per_row),
            float(weight_bytes(c) + kv_bytes(c, batch, cache_len)
                  + 2 * state_bytes(c, batch)))


# ----------------------------------------------------------------- weights
def _dtypes():
    """Storage type of each leaf, as the program keeps it."""
    return {"A_log": F32, "D_skip": F32}


def init_layer(key, layer, c) -> Dict[str, jnp.ndarray]:
    """Mamba2 layer ``layer``'s weights in float32, a pure function of
    (key, layer), drawn as HF's ``Zamba2PreTrainedModel`` draws them:
    projections normal(0, initializer_range), conv uniform ±1/sqrt(d_conv),
    A = 1..heads, dt log-uniform in [time_step_min, time_step_max] (floored)
    through inverse softplus, D and the norms one."""
    s = sizes(c)
    k = jax.random.fold_in(key, layer)
    r = lambda i: jax.random.fold_in(k, i)
    D, Di, N, H, G, K = s["D"], s["Di"], s["N"], s["H"], s["G"], s["K"]
    bound = 1.0 / math.sqrt(K)
    dt = jnp.exp(jax.random.uniform(r(5), (H,), F32, math.log(s["dt_min"]),
                                    math.log(s["dt_max"])))
    dt = jnp.maximum(dt, s["dt_floor"])
    return {
        "ln": jnp.ones((D,), F32),
        "in_proj": s["std"] * jax.random.normal(r(1), (D, 2 * Di + 2 * G * N + H), F32),
        "conv_w": jax.random.uniform(r(2), (K, s["conv_dim"]), F32, -bound, bound),
        "conv_b": jax.random.uniform(r(3), (s["conv_dim"],), F32, -bound, bound),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=F32)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "D_skip": jnp.ones((H,), F32),
        "gate_ln": jnp.ones((Di,), F32),
        "out_proj": s["std"] * jax.random.normal(r(6), (Di, D), F32),
    }


def init_block(key, block, c) -> Dict[str, jnp.ndarray]:
    """Shared block ``block``'s weights in float32 (projections as (in, out))."""
    s = sizes(c)
    k = jax.random.fold_in(jax.random.fold_in(key, 1 << 21), block)
    n = lambda i, shape: s["std"] * jax.random.normal(jax.random.fold_in(k, i), shape, F32)
    AD, D, F, nh, nkv, hd = s["AD"], s["D"], s["F"], s["nh"], s["nkv"], s["hd"]
    return {"in_norm": jnp.ones((AD,), F32), "wq": n(1, (AD, nh * hd)),
            "wk": n(2, (AD, nkv * hd)), "wv": n(3, (AD, nkv * hd)),
            "wo": n(4, (nh * hd, D)), "ff_norm": jnp.ones((D,), F32),
            "w1": n(5, (D, F)), "w3": n(6, (D, F)), "w2": n(7, (F, D))}


def init_app(key, app, c) -> Dict[str, jnp.ndarray]:
    """Application ``app``'s LoRA (a, then b1 | b3 for gate | up) and output
    linear in float32."""
    s = sizes(c)
    k = jax.random.fold_in(jax.random.fold_in(key, 1 << 22), app)
    n = lambda i, shape: s["std"] * jax.random.normal(jax.random.fold_in(k, i), shape, F32)
    D, F, r = s["D"], s["F"], s["r"]
    return {"a": n(1, (D, r)), "b1": n(2, (r, F)), "b3": n(3, (r, F)),
            "lin": n(4, (D, D))}


def init_globals(key, c):
    s = sizes(c)
    k = jax.random.fold_in(key, 1 << 20)
    return {"emb": s["std"] * jax.random.normal(k, (s["V"], s["D"]), F32),
            "ln_f": jnp.ones((s["D"],), F32)}


def _store(tree, dtype):
    keep = _dtypes()
    return {k: (_store(v, dtype) if isinstance(v, dict)
                else v.astype(keep.get(k, dtype))) for k, v in tree.items()}


def _stored_f32(tree, dtype):
    """float32 values as ``_store`` would store them (``reduce_precision``,
    which the compiler keeps where a cast down and up may be dropped)."""
    keep = _dtypes()

    def rnd(k, v):
        dt = jnp.dtype(keep.get(k, dtype))
        if dt == F32:
            return v.astype(F32)
        fi = jnp.finfo(dt)
        return jax.lax.reduce_precision(v.astype(F32), exponent_bits=fi.nexp,
                                        mantissa_bits=fi.nmant)

    return {k: (_stored_f32(v, dtype) if isinstance(v, dict) else rnd(k, v))
            for k, v in tree.items()}


def make_weights(key, c, dtype=jnp.bfloat16):
    """All weights, stored as ``dtype`` one layer, block or application at a
    time (no float32 copy of the whole model is held)."""
    s = sizes(c)
    each = lambda init, n: jax.lax.map(lambda i: _store(init(key, i, c), dtype),
                                       jnp.arange(n))
    return {"layers": each(init_layer, s["L"]), "blocks": each(init_block, s["NB"]),
            "apps": each(init_app, s["A"]), **_store(init_globals(key, c), dtype)}


# --------------------------------------------------------------- reference
def identity(x):
    return x


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x: (R, T, n, hd); rotate-half, as HF's ``apply_rotary_pos_emb``."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = positions.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attend(q, k, v, scale, rnd):
    """Causal softmax attention over (R, T, n, hd), Q_ROWS query rows at a
    time (the last block padded with rows that are dropped)."""
    R, T, nh, hd = q.shape
    k = jnp.repeat(k, nh // k.shape[2], axis=2)
    v = jnp.repeat(v, nh // v.shape[2], axis=2)
    rows = min(Q_ROWS, T)
    nb = -(-T // rows)
    qb = jnp.pad(q, ((0, 0), (0, nb * rows - T), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(R, nb, rows, nh, hd), 1, 0)

    def one(args):
        i, qi = args
        sc = jnp.einsum("rqhd,rkhd->rhqk", rnd(qi), rnd(k), precision=HI) * scale
        causal = (i * rows + jnp.arange(rows))[:, None] >= jnp.arange(T)[None, :]
        pr = jax.nn.softmax(jnp.where(causal[None, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("rhqk,rkhd->rqhd", rnd(pr), rnd(v), precision=HI)

    out = jax.lax.map(one, (jnp.arange(nb), qb))
    return jnp.moveaxis(out, 0, 1).reshape(R, nb * rows, nh, hd)[:, :T]


def _shared(x, x0, bp, ap, s, rnd: Callable):
    """One application: T of shape (R, T, D)."""
    R, T, _ = x.shape
    nh, nkv, hd = s["nh"], s["nkv"], s["hd"]
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HI)
    u = _rms(jnp.concatenate([x, x0], axis=-1), bp["in_norm"], s["eps"])
    pos = jnp.arange(T)
    q = _rope(mm(u, bp["wq"]).reshape(R, T, nh, hd), pos, s["theta"])
    k = _rope(mm(u, bp["wk"]).reshape(R, T, nkv, hd), pos, s["theta"])
    v = mm(u, bp["wv"]).reshape(R, T, nkv, hd)
    o = _attend(q, k, v, (hd / 2) ** -0.5, rnd)
    m = _rms(mm(o.reshape(R, T, nh * hd), bp["wo"]), bp["ff_norm"], s["eps"])
    lo = mm(m, ap["a"])
    g = mm(m, bp["w1"]) + mm(lo, ap["b1"])
    up = mm(m, bp["w3"]) + mm(lo, ap["b3"])
    f = mm(jax.nn.gelu(g, approximate=False) * up, bp["w2"])
    return mm(f, ap["lin"])


def _mixer(h, p, s, rnd: Callable):
    """The Mamba2 mixer of one layer on its normed input h: (R, T, D)."""
    R, T, _ = h.shape
    Di, N, H, P, G, K = s["Di"], s["N"], s["H"], s["P"], s["G"], s["K"]
    zx = jnp.matmul(rnd(h), rnd(p["in_proj"]), precision=HI)
    z, xbc, dt = zx[..., :Di], zx[..., Di:2 * Di + 2 * G * N], zx[..., 2 * Di + 2 * G * N:]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + T, :] * p["conv_w"][i] for i in range(K)) + p["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :Di].reshape(R, T, H, P)
    Bm = jnp.repeat(xbc[..., Di:Di + G * N].reshape(R, T, G, N), H // G, axis=2)
    Cm = jnp.repeat(xbc[..., Di + G * N:].reshape(R, T, G, N), H // G, axis=2)
    dt = jnp.maximum(jax.nn.softplus(dt + p["dt_bias"]), s["dt_min"])  # (R, T, H)
    A = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (state * jnp.exp(dt_t * A)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    tm = lambda a: jnp.moveaxis(a, 1, 0)
    _, ys = jax.lax.scan(step, jnp.zeros((R, H, P, N), F32),
                         (tm(xs), tm(Bm), tm(Cm), tm(dt)))
    y = (tm(ys) + xs * p["D_skip"][:, None]).reshape(R, T, Di) * jax.nn.silu(z)
    y = _rms(y.reshape(R, T, G, Di // G), 1.0, s["eps"]).reshape(R, T, Di) * p["gate_ln"]
    return jnp.matmul(rnd(y), rnd(p["out_proj"]), precision=HI)


def _forward(c, tokens, glob, layer, block, app, rnd: Callable):
    """The final-normed hidden states (R, T, D) of ``tokens`` (R, T); the
    weights come from ``layer(l)``, ``block(b)``, ``app(j)`` and ``glob``."""
    s = sizes(c)
    x0 = glob["emb"][tokens]
    x = x0
    bounds = [0, *s["ids"], s["L"]]
    for j in range(len(bounds) - 1):
        t = None
        if j:
            t = _shared(x, x0, block((j - 1) % s["NB"]), app(j - 1), s, rnd)
        first = bounds[j]

        def body(x, l, t=t, first=first):
            h = x if t is None else jnp.where(l == first, x + t, x)
            p = layer(l)
            return x + _mixer(_rms(h, p["ln"], s["eps"]), p, s, rnd), None

        if bounds[j + 1] > first:
            x, _ = jax.lax.scan(body, x, jnp.arange(first, bounds[j + 1]))
    return _rms(x, glob["ln_f"], s["eps"])


def _from_key(key, c, dtype):
    """Weight getters that make each part from ``key`` as the configuration
    stores it (``dtype``) where it is used."""
    store = lambda t: _stored_f32(t, dtype)
    return (store(init_globals(key, c)), lambda l: store(init_layer(key, l, c)),
            lambda b: store(init_block(key, b, c)), lambda j: store(init_app(key, j, c)))


def _from_tree(w):
    """Weight getters over a tree as ``make_weights`` builds it."""
    pick = lambda tree: lambda i: jax.tree.map(
        lambda a: jnp.asarray(a, F32)[i], tree)
    glob = {k: jnp.asarray(w[k], F32) for k in ("emb", "ln_f")}
    return glob, pick(w["layers"]), pick(w["blocks"]), pick(w["apps"])


def _logits(x, emb, rnd):
    return jnp.matmul(rnd(x), rnd(emb).T, precision=HI)


def logits(key, c, tokens, positions, rnd: Callable = identity, dtype=jnp.bfloat16):
    """Reference logits (R, len(positions), V) of the sequences ``tokens``
    (R, T) at ``positions``, weights made from ``key``."""
    glob, *get = _from_key(key, c, dtype)
    x = _forward(c, tokens, glob, *get, rnd)
    return _logits(x[:, positions], glob["emb"], rnd)


def loss(w, tokens, c, rnd: Callable = identity):
    """Mean next-token cross-entropy of the sequences ``tokens`` (R, T) under
    the weight tree ``w`` (float32)."""
    glob, *get = _from_tree(w)
    lg = _logits(_forward(c, tokens, glob, *get, rnd)[:, :-1], glob["emb"], rnd)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)


# ----------------------------------------------------------- program map
def to_program(w):
    """The program's tree (``models/hybrid.hybrid_param_defs``)."""
    b, a = w["blocks"], w["apps"]
    return {"emb": w["emb"], "ln_f": w["ln_f"], "mamba": w["layers"],
            "blocks": {"in_norm": b["in_norm"], "wq": b["wq"], "wk": b["wk"],
                       "wv": b["wv"], "wo": b["wo"], "ff_norm": b["ff_norm"],
                       "mlp": {"w1": b["w1"], "w3": b["w3"], "w2": b["w2"]}},
            "adapters": a}


def program_config(c):
    """The program's ModelConfig for this file, refused where it would run
    other sizes or another layout than the file states."""
    import dataclasses

    from repro.configs.base import get_config

    p = c["program"]
    over = {k: tuple(v) if isinstance(v, list) else v
            for k, v in p.get("overrides", {}).items()}
    mc = dataclasses.replace(get_config(p["arch"]), **over)
    s = sizes(c)
    want = {"family": "hybrid", "mlp": "geglu", "n_layers": s["L"], "d_model": s["D"],
            "n_heads": s["nh"], "n_kv_heads": s["nkv"], "head_dim": s["hd"],
            "d_ff": s["F"], "vocab": s["V"], "rope_theta": s["theta"],
            "tie_embeddings": c["tie_word_embeddings"], "ssm_state": s["N"],
            "ssm_expand": c["mamba_expand"], "ssm_headdim": s["P"],
            "ssm_heads": s["H"], "ssm_conv": s["K"], "ssm_chunk": s["Q"],
            "ssm_ngroups": s["G"], "ssm_dt_min": s["dt_min"],
            "hybrid_layer_ids": tuple(s["ids"]), "n_mem_blocks": s["NB"],
            "adapter_rank": s["r"]}
    bad = {k: (getattr(mc, k), v) for k, v in want.items() if getattr(mc, k) != v}
    if (bad or s["eps"] != 1e-5 or s["AD"] != 2 * s["D"] or c["hidden_act"] != "gelu"
            or c["use_shared_attention_adapter"] or not c["use_shared_mlp_adapter"]
            or not c["use_mem_rope"] or c["use_long_context"] or c["add_bias_linear"]
            or not c["use_conv_bias"]):
        raise ValueError(f"program config {p} departs from the file: {bad}")
    return mc
