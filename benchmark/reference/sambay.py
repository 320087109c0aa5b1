"""The plain reference of the decoder-hybrid-decoder family (SambaY with
differential attention, arXiv:2507.06607; the configuration's own keys for
every size, its ``assumed`` for what ``config.json`` lacks): float32
``jax.numpy`` at ``precision="highest"``, written from the layer equations, with
no kernel, no loop over stacked layers, no batching tricks and nothing
imported from the program. It takes the program's parameter tree (``first``: the
(Mamba, windowed attention) pairs stacked, ``mid``: the two hand-off layers,
``last``: the (memory unit, cross-attention) pairs stacked) and a configuration
file's content.

Stream (0-indexed layers, ``L`` of them, ``d`` wide): ``x = E[ids]``; each layer
``x = x + mixer_i(LN(x))`` then ``x = x + MLP(LN'(x))``; ``logits = LN_f(x) E^T``.
``LN`` is LayerNorm with mean, gain and bias. ``MLP(u)``: ``(g, p) = split(u W_1)``;
``(p * silu(g)) W_2``. The mixer of layer ``i``:

- even ``i <= L/2`` — Mamba: ``(x', z) = split(u W_in)``; ``x'' = silu(conv(x') +
  b_c)`` (depthwise, causal, ``d_conv`` taps, the last on the token itself);
  ``(r, B, C) = x'' W_x``; ``Delta = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``h_t = exp(Delta_t A) h_{t-1} + (Delta_t x''_t) B_t^T`` from ``h = 0``, ONE
  TOKEN AT A TIME (``lax.scan`` over the tokens); ``y_t = h_t C_t + D x''_t``; out
  ``(y * silu(z)) W_out``. Layer ``L/2`` hands ``M = y`` down.
- even ``i > L/2`` — gated memory unit: ``(silu(u W_1') * M) W_2'``.
- odd ``i`` — differential attention: ``(q, k, v) = u W_qkv + b`` (a cross layer,
  ``i > L/2 + 1``: ``q = u W_q + b_q`` and layer ``L/2 + 1``'s ``k, v``); differential
  head ``j`` has ``(q1, q2) = (q[2j], q[2j+1])``, its key/value pair ``p = j // (H /
  Hk)`` has ``(k1, k2) = (k[2p], k[2p+1])`` and ``v = [v[2p], v[2p+1]]``; ``a_n =
  softmax(q_n k_n^T / sqrt(e) + mask) v``; ``o = a_1 - lambda a_2``, ``lambda =
  exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 i)``; ``o = RMSNorm(o) * (1 - lambda_init)`` per differential head; out
  ``concat(o) W_o + b_o``. Mask: causal; for ``i < L/2`` also ``q - k <
  sliding_window`` (a query sees itself and ``sliding_window - 1`` tokens before
  it). The softmaxes are dense, ``QUERY_BLOCK`` queries at a time against every key.

It runs sub-block by sub-block, each one small jitted program whose float32
casts of its weights live only inside the call, and the head a block of the
vocabulary at a time straight to the host, so that it fits on the chip beside
the 7.7 GB of bf16 weights: the largest float32 cast is an MLP's (315 MB), the
largest activation a query block's scores (335 MB).

``compute`` (default float32) is the type every weight and activation is cast
to and every product returns: ``jnp.bfloat16`` gives the reading "the nearest
precision below" of PERF.md section 4, which the tolerance must refuse.
``fault`` names ONE departure from the equations, for the readings the
tolerance is set against (``benchmark/tools/sambay_readings.py``):
``state_bf16`` (the scan's state kept in bf16), ``no_d`` (``y`` without ``D
x''``), ``lambda0`` (``lambda`` taken as 0), ``window_plus_one`` (a window one
token wider), ``own_kv`` (a cross layer computes keys and values from its OWN
normed input with layer ``L/2 + 1``'s weights instead of reading that layer's).

A dense model routes nothing: there is no routing slack, and
``drivers/offline_tokens_dense.py`` holds every token to both limits.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512  # queries whose scores against every key are held at a time
VOCAB_BLOCK = 16384  # ids whose logits are computed, and moved to the host, at a time
FAULTS = ("", "state_bf16", "no_d", "lambda0", "window_plus_one", "own_kv")


def _mm(spec: str, a, b, compute):
    return jnp.einsum(spec, a.astype(compute), b.astype(compute), precision="highest", preferred_element_type=compute)


def layer_norm(x, p: Dict, eps: float, compute=jnp.float32):
    x = x.astype(compute)
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    scaled = centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps)
    return (scaled * p["gain"].astype(compute) + p["bias"].astype(compute)).astype(compute)


def rms_norm(x, gain, eps: float, compute=jnp.float32):
    x = x.astype(compute)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(compute)).astype(compute)


def head_dim(cfg: Dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * float(np.exp(-0.3 * layer))


def diff_lambda(vectors, layer: int, compute=jnp.float32):
    """``exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init`` from ``(4, e)``."""
    l = vectors.astype(compute).astype(jnp.float32)
    return jnp.exp(jnp.sum(l[0] * l[1])) - jnp.exp(jnp.sum(l[2] * l[3])) + lambda_init(layer)


# ---- the sublayers -----------------------------------------------------------


def mlp(cfg: Dict, p: Dict, x, compute=jnp.float32):
    """``x + MLP(LN(x))``."""
    u = layer_norm(x, p["norm"], cfg["layer_norm_eps"], compute)
    gate, up = jnp.split(_mm("bsd,df->bsf", u, p["w1"], compute), 2, axis=-1)
    return (x + _mm("bsf,fd->bsd", (up * jax.nn.silu(gate)).astype(compute), p["w2"], compute)).astype(compute)


def mamba_inputs(cfg: Dict, p: Dict, x, compute=jnp.float32):
    """``(x'', z, Delta, A, B, C)`` of a Mamba layer for the stream ``x (B, S, D)``."""
    n, r, taps = cfg["assumed"]["d_state"], cfg["assumed"]["dt_rank"], cfg["assumed"]["d_conv"]
    s = x.shape[1]
    u = layer_norm(x, p["norm"], cfg["layer_norm_eps"], compute)
    xi, z = jnp.split(_mm("bsd,de->bse", u, p["in"], compute), 2, axis=-1)
    padded = jnp.pad(xi, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, j : j + s] * p["conv"][j].astype(compute) for j in range(taps)) + p["conv_bias"].astype(compute)
    xc = jax.nn.silu(conv.astype(compute)).astype(compute)
    low = _mm("bse,er->bsr", xc, p["x"], compute)
    step = _mm("bsr,re->bse", low[..., :r], p["dt"], compute) + p["dt_bias"].astype(compute)
    delta = jax.nn.softplus(step.astype(compute)).astype(compute)
    a = -jnp.exp(p["a_log"].astype(compute))
    return xc, z.astype(compute), delta, a.astype(compute), low[..., r : r + n], low[..., r + n :]


def selective_scan(xc, delta, a, b, c, d, compute=jnp.float32, state=None, with_d: bool = True):
    """The recurrence one token at a time: ``y (B, S, C)``. ``state`` is the
    type the state is kept in (``compute`` unless a reading asks otherwise)."""
    state = state or compute
    a, d = a.astype(compute), d.astype(compute)

    def token(h, inputs):
        x_t, dt, b_t, c_t = inputs  # (B, C), (B, C), (B, N), (B, N)
        decay = jnp.exp((dt[..., None] * a).astype(compute))
        h = (decay * h.astype(compute) + (dt * x_t)[..., None] * b_t[:, None, :]).astype(state)
        y = jnp.sum((h.astype(compute) * c_t[:, None, :]).astype(compute), axis=-1)
        return h, (y + d * x_t if with_d else y).astype(compute)

    tokens = tuple(jnp.moveaxis(v.astype(compute), 1, 0) for v in (xc, delta, b, c))
    _last, y = jax.lax.scan(token, jnp.zeros((xc.shape[0], *a.shape), state), tokens)
    return jnp.moveaxis(y, 0, 1)


def mamba_output(p: Dict, x, y, z, compute=jnp.float32):
    """``x + (y * silu(z)) W_out``."""
    gated = (y.astype(compute) * jax.nn.silu(z)).astype(compute)
    return (x + _mm("bse,ed->bsd", gated, p["out"], compute)).astype(compute)


def gmu(cfg: Dict, p: Dict, x, memory, compute=jnp.float32):
    """``x + (silu(LN(x) W_1) * M) W_2``."""
    u = layer_norm(x, p["norm"], cfg["layer_norm_eps"], compute)
    gate = jax.nn.silu(_mm("bsd,de->bse", u, p["w1"], compute))
    return (x + _mm("bse,ed->bsd", (gate * memory.astype(compute)).astype(compute), p["w2"], compute)).astype(compute)


def attn_projections(cfg: Dict, p: Dict, x, compute=jnp.float32):
    """``u W + b`` of an attention layer split by head: ``(q (B, S, H, e), k, v
    (B, S, Hk, e))``, or ``(q, None, None)`` of a cross layer."""
    e, h, hk = head_dim(cfg), cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b, s, _ = x.shape
    u = layer_norm(x, p["norm"], cfg["layer_norm_eps"], compute)
    qkv = (_mm("bsd,de->bse", u, p["qkv"], compute) + p["qkv_bias"].astype(compute)).astype(compute)
    q = qkv[..., : h * e].reshape(b, s, h, e)
    if qkv.shape[-1] == h * e:
        return q, None, None
    k, v = jnp.split(qkv[..., h * e :], 2, axis=-1)
    return q, k.reshape(b, s, hk, e), v.reshape(b, s, hk, e)


def diff_heads(cfg: Dict, q, k, v, lam, lam0, gain, first_query, window, compute=jnp.float32):
    """The differential heads' outputs ``(B, Sq, H e)`` for the queries ``q (B,
    Sq, H, e)`` at positions ``first_query ...`` against every key ``k, v (B, S,
    Hk, e)``: two dense masked softmaxes over one value, their difference, the
    per-head norm. ``window`` 0: causal alone."""
    e, h, hk = head_dim(cfg), cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b, sq = q.shape[:2]
    s = k.shape[1]
    group = h // hk  # differential heads that share one pair of keys and its value
    q = q.reshape(b, sq, h // 2, 2, e)
    k = jnp.repeat(k.reshape(b, s, hk // 2, 2, e), group, axis=2)
    v = jnp.repeat(v.reshape(b, s, hk // 2, 2 * e), group, axis=2)
    rows = first_query + jnp.arange(sq)[:, None]
    keys = jnp.arange(s)[None, :]
    seen = rows >= keys
    if window:
        seen = seen & (rows - keys < window)
    out = []
    for n in range(2):
        scores = _mm("bqhe,bkhe->bhqk", q[..., n, :], k[..., n, :], compute) * e**-0.5
        probs = jax.nn.softmax(jnp.where(seen, scores.astype(compute), -jnp.inf), axis=-1)
        out.append(_mm("bhqk,bkhf->bqhf", probs.astype(compute), v, compute))
    o = (out[0] - lam.astype(compute) * out[1]).astype(compute)
    o = rms_norm(o, gain, cfg["layer_norm_eps"], compute) * jnp.asarray(1.0 - lam0, compute)
    return o.astype(compute).reshape(b, sq, h * e)


def attn_output(p: Dict, x, o, compute=jnp.float32):
    return (x + _mm("bse,ed->bsd", o, p["o"], compute) + p["o_bias"].astype(compute)).astype(compute)


# ---- the model ---------------------------------------------------------------


def _blocks(cfg: Dict, compute, fault: str = "") -> Dict:
    """The sub-blocks as functions of arrays alone, the configuration closed over."""
    state = jnp.bfloat16 if fault == "state_bf16" else None
    return {
        "embed": lambda table, ids: table[ids].astype(compute),
        "mlp": lambda p, x: mlp(cfg, p, x, compute),
        "mamba_inputs": lambda p, x: mamba_inputs(cfg, p, x, compute),
        "scan": lambda *arrays: selective_scan(*arrays, compute, state, with_d=fault != "no_d"),
        "mamba_output": lambda p, x, y, z: mamba_output(p, x, y, z, compute),
        "gmu": lambda p, x, memory: gmu(cfg, p, x, memory, compute),
        "attn_projections": lambda p, x: attn_projections(cfg, p, x, compute),
        "diff_heads": lambda q, k, v, lam, lam0, gain, first_query, window: diff_heads(
            cfg, q, k, v, lam, lam0, gain, first_query, window, compute
        ),
        "attn_output": lambda p, x, o: attn_output(p, x, o, compute),
        "final_norm": lambda p, x: layer_norm(x, p, cfg["layer_norm_eps"], compute),
        "head": lambda u, rows: _mm("bsd,vd->bsv", u, rows, compute).astype(jnp.float32),
    }


def _jitted(cfg: Dict, compute, fault: str = "") -> Dict:
    """Each sub-block as one jitted program, so that the float32 casts of a
    sub-block's weights live only inside its call."""
    static = {"diff_heads": (7,)}  # the window decides the mask's form
    return {name: jax.jit(fn, static_argnums=static.get(name, ())) for name, fn in _blocks(cfg, compute, fault).items()}


def layer_params(cfg: Dict, params: Dict, i: int) -> Tuple[Dict, Dict]:
    """``(mixer parameters, MLP parameters)`` of layer ``i`` out of the three
    parts of the program's tree."""
    half = cfg["num_hidden_layers"] // 2
    if i in (half, half + 1):
        part = params["mid"]
    elif i < half:
        part = jax.tree.map(lambda leaf: leaf[i // 2], params["first"])
    else:
        part = jax.tree.map(lambda leaf: leaf[(i - half - 2) // 2], params["last"])
    mixer = "attn" if i % 2 else ("gmu" if i > half else "mamba")
    return part[mixer], part["mlp_b" if i % 2 else "mlp_a"]


def mamba_layer(cfg: Dict, p: Dict, x, run: Dict):
    """``(x + Mamba(LN(x)), y)``: ``y`` is what layer ``L/2`` hands down."""
    xc, z, delta, a, b, c = run["mamba_inputs"](p, x)
    y = run["scan"](xc, delta, a, b, c, p["d"])
    return run["mamba_output"](p, x, y, z), y


def attn_layer(cfg: Dict, p: Dict, x, i: int, run: Dict, compute=jnp.float32, kv=None, fault: str = "", own=None):
    """``(x + DiffAttn(LN(x)), (k, v))`` of layer ``i``; a cross layer is given
    ``kv``. ``own`` (the fault ``own_kv`` alone): layer ``L/2 + 1``'s parameters,
    with which a cross layer then makes keys and values of its own input."""
    half = cfg["num_hidden_layers"] // 2
    window = 0 if i > half else cfg["sliding_window"] + (fault == "window_plus_one")
    q, k, v = run["attn_projections"](p, x)
    if k is None:
        k, v = run["attn_projections"]({**own, "norm": p["norm"]}, x)[1:] if own is not None else kv
    lam0 = lambda_init(i)
    lam = jnp.float32(0.0) if fault == "lambda0" else diff_lambda(p["lambda"], i, compute)
    o = jnp.concatenate(
        [
            run["diff_heads"](q[:, s0 : s0 + QUERY_BLOCK], k, v, lam, lam0, p["subln"], s0, window)
            for s0 in range(0, x.shape[1], QUERY_BLOCK)
        ],
        axis=1,
    )
    return run["attn_output"](p, x, o), (k, v)


def forward(cfg: Dict, params: Dict, ids, compute=jnp.float32, fault: str = "") -> np.ndarray:
    """Reference logits ``(B, S, V)``, float32, on the host."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is not one of {FAULTS}")
    run = _jitted(cfg, compute, fault)
    layers, half = cfg["num_hidden_layers"], cfg["num_hidden_layers"] // 2
    assert params["first"]["mamba"]["in"].shape[0] == layers // 4
    assert params["last"]["gmu"]["w1"].shape[0] == layers // 4 - 1
    x = run["embed"](params["embed"], ids)
    memory = kv = None
    for i in range(layers):
        mixer, mlp_p = layer_params(cfg, params, i)
        if i % 2:
            own = params["mid"]["attn"] if fault == "own_kv" and i > half + 1 else None
            x, made = attn_layer(cfg, mixer, x, i, run, compute, kv, fault, own)
            kv = made if i == half + 1 else kv
        elif i > half:
            x = run["gmu"](mixer, x, memory)
        else:
            x, y = mamba_layer(cfg, mixer, x, run)
            memory = y if i == half else memory
        x = run["mlp"](mlp_p, x)
    u = run["final_norm"](params["final_norm"], x)
    vocab = params["embed"].shape[0]
    size = -(-vocab // -(-vocab // VOCAB_BLOCK))  # equal blocks of at most VOCAB_BLOCK ids
    logits = np.empty((*ids.shape, vocab), np.float32)
    for v0 in range(0, vocab, size):
        logits[..., v0 : v0 + size] = np.asarray(run["head"](u, params["embed"][v0 : v0 + size]))
    return logits
