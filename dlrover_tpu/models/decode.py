"""KV-cache autoregressive decoding for the Llama-family models, TPU-first.

The reference delegates generation to vLLM/Megatron inside its RL examples
(SURVEY.md §2.5); a from-scratch TPU stack owns the rollout path. Design
for XLA:

- **static shapes end to end**: the cache is a tuple of fixed head-major
  ``(B, KV, T, Dh)`` buffers, one per layer (see ``init_kv_cache`` for
  why per-layer, not layer-stacked); each step writes one position via
  ``dynamic_update_slice`` and masks scores past ``pos`` — no growing
  arrays, so the whole generate loop is ONE compiled program
  (``lax.scan``), not a recompile per length (the naive concat loop
  recompiles at every new sequence length);
- **the layer loop is UNROLLED in the decode step** so each buffer's
  update is a ``dynamic_update_slice`` whose operand dies at the update
  — the shape XLA's in-place-DUS optimization matches for while-loop
  carries. The r3 design scanned layers with per-layer cache slices as
  scan xs/ys and paid ~2 full cache copies per step in ys re-stacking
  (~13 ms/step at 2k ctx); a layer scan CARRYING one stacked (L,…)
  buffer is worse still — XLA copies the whole stack at every layer's
  DUS (measured 36.6 ms/step). Unrolled per-layer buffers measured
  4.5 ms/step on v5e — 78% of the HBM roof;
- **prefill is a single batched pass**: the prompt runs through the dense
  causal forward once, k/v captured per layer on the way — MXU-shaped,
  not token-at-a-time;
- decode steps are memory-bound matvecs by nature; keeping params bf16
  and the cache bf16 halves the HBM traffic that dominates them;
- sampling (temperature / top-k) happens in f32 inside the same program.

Works with ``llama.init_params`` AND ``moe.init_params`` pytrees (stacked
layers): the FFN half of each decode step dispatches on the config — a
MoE config routes the single position through its experts (the dispatch
einsums collapse to top-k expert matvecs at S=1; the KV cache itself is
attention-only, so nothing expert-specific needs caching).
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.common.constants import ConfigKey, env_str
from dlrover_tpu.common.log import log_once
from dlrover_tpu.models.llama import _mlp, _rms_norm, _rope

# K-block size of the fused decode kernel; caches sized in multiples of
# this can take the pallas path
_DECODE_BLOCK_K = 256


def flash_decode_wanted(T: int, quantized: bool,
                        live_len: Optional[int] = None) -> bool:
    """Should the single-token attend use the fused pallas kernel?

    Auto policy (measured on v5e; r4 final — fused-batch kernel grid +
    scale-folding, ops/flash_attention.py):
    - int8 cache → yes: the kernel reads int8 + per-vector scales
      straight from HBM, converts in VMEM, and folds the scales into
      the (rows x block) score/probability planes instead of scaling
      the K/V blocks (head_dim x fewer VPU multiplies). At 2k ctx this
      is the FASTEST decode path: 235-261 steps/s = 69-76% of the int8
      roof (1881-2088 tok/s at batch 8) vs tight bf16's 1621-1754
      tok/s across runs — int8 won every same-run pair by 14-25% — at
      HALF the cache HBM: capacity AND throughput. The XLA dequant
      path (kernel off) materializes a bf16 copy and trails both;
    - bf16 cache → only when the cache is meaningfully larger than the
      live context (preallocated serving cache): the kernel skips blocks
      past ``pos`` at ~zero bandwidth. On a fully-live cache the
      fused-batch kernel now MATCHES XLA's einsum step-for-step (200.7
      vs 201.3 steps/s at 2k), but a tight einsum cache still avoids
      the kernel's block padding — so right-sized caches keep the
      einsum and nothing is left on the table either way.
    ``DLROVER_TPU_FLASH_DECODE=1/0`` force-overrides; default is auto.
    ``live_len`` is the statically-known context the cache will actually
    hold (prompt + budget) when the caller knows it; None means assume
    the cache is fully live.
    """
    env = env_str(ConfigKey.FLASH_DECODE, "auto")
    if env in ("0", "off"):
        return False
    if T % _DECODE_BLOCK_K != 0 or jax.default_backend() != "tpu":
        log_once(
            "decode attention: XLA einsum path, fused kernel not eligible "
            "(cache length %s %% %s = %s, default backend %r)",
            T, _DECODE_BLOCK_K, T % _DECODE_BLOCK_K, jax.default_backend(),
        )
        return False
    if env == "1":
        return True
    if quantized:
        # fused int8 traffic ≈ T bytes/vector vs einsum ≈ live_len int8 +
        # 2×live_len bf16 materialized + read back (~5×live_len): the
        # kernel wins unless block padding dwarfs the live context (tiny
        # prompts rounded up to one 256 block)
        return live_len is None or T <= live_len * 4
    # bf16: worth it only when the kernel can actually SKIP cache blocks
    # the einsum would read — needs both a 2x size ratio and at least one
    # whole skippable block (else a short context padded up to one block
    # reads MORE than a tight einsum cache, up to block_k/live_len times)
    return (
        live_len is not None
        and T >= live_len * 2
        and T - live_len >= _DECODE_BLOCK_K
    )


def _ffn(xn, layer, config) -> jnp.ndarray:
    """Dense SwiGLU or routed-expert FFN, by config family."""
    if getattr(config, "n_experts", 0):
        import dataclasses

        from dlrover_tpu.models.moe import _moe_ffn

        # route per token: a training route_group_size can't divide the
        # S=1 decode token count, and grouping unrelated batch rows would
        # let capacity drops zero out tokens — per-token groups make
        # capacity >= top_k, so nothing drops at decode
        if config.route_group_size is not None:
            config = dataclasses.replace(config, route_group_size=None)
        out, _ = _moe_ffn(xn, layer, config)  # aux loss unused at decode
        return out
    return _mlp(xn, layer)


def init_kv_cache(config, batch: int, max_len: Optional[int] = None,
                  quantize: bool = False) -> Dict:
    """Fixed-size key/value buffers + the write position. Each cache
    field is a TUPLE of per-layer arrays.

    Per-buffer layout is HEAD-MAJOR ``(B, KV, T, Dh)``: the decode
    attend contracts over (T, Dh) per head, and keeping a head's
    timeline contiguous is worth +24% on the attention einsum at 2k
    context (measured on v5e vs the token-major layout) — and lets the
    fused kernel read blocks without an in-VMEM transpose.

    Per-LAYER buffers (not one stacked ``(L, …)`` array) because decode
    throughput lives or dies on XLA updating the cache in place inside
    the token loop: a separate buffer per layer, written once per step
    by the unrolled layer loop, is the pattern XLA's in-place
    dynamic-update-slice optimization matches for while-loop carries.
    One stacked buffer updated at a traced layer index inside a layer
    scan is NOT matched — XLA materializes a full copy of the stack per
    layer, measured 8x slower end-to-end (36.6 vs 4.5 ms/step, v5e,
    1B params, 2k context).

    ``quantize=True`` stores int8 k/v with per-vector f32 scales
    (absmax over head_dim): the cache is the memory term that grows with
    context, so int8 DOUBLES the max context per HBM at ~0.4%
    per-element error (which the attention softmax washes out further).
    int8 is the capacity knob AND (with the fused kernel's scale-folding,
    r4 final) the long-context throughput path: at 2k ctx it decodes 14-25%
    faster than tight bf16 (same-run pairs) — the saved bandwidth finally outruns the
    dequant work — while short contexts are a wash (see
    flash_decode_wanted for the measured numbers).
    """
    c = config
    T = max_len or c.max_seq_len
    shape = (batch, c.n_kv_heads, T, c.head_dim)
    L = c.n_layers
    if quantize:
        sshape = shape[:-1]
        return {
            "k": tuple(jnp.zeros(shape, jnp.int8) for _ in range(L)),
            "v": tuple(jnp.zeros(shape, jnp.int8) for _ in range(L)),
            "k_scale": tuple(
                jnp.zeros(sshape, jnp.float32) for _ in range(L)),
            "v_scale": tuple(
                jnp.zeros(sshape, jnp.float32) for _ in range(L)),
            "pos": jnp.zeros((), jnp.int32),
        }
    return {
        "k": tuple(jnp.zeros(shape, c.dtype) for _ in range(L)),
        "v": tuple(jnp.zeros(shape, c.dtype) for _ in range(L)),
        "pos": jnp.zeros((), jnp.int32),
    }


def _quantize(x):
    """(…, D) → int8 values + f32 absmax/127 scales over the last axis."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    safe = jnp.maximum(scale, 1e-9)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / safe[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def _dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _split_heads(x, n_heads, head_dim):
    B, S, _ = x.shape
    return x.reshape(B, S, n_heads, head_dim)


def _attend(q, k, v, mask, scale, pos=None, flash=False,
            k_scale=None, v_scale=None):
    """q (B,Q,H,Dh) against head-major k/v (B,KV,T,Dh), grouped-query;
    mask broadcastable to (B,1,Q,T). f32 softmax.

    GQA via a grouped einsum, NOT ``jnp.repeat``: decode is bound by
    reading the cache, and materializing K/V ``groups`` times would
    multiply exactly that traffic. Head-major keeps each head's timeline
    contiguous for the (T, Dh) contraction (+24% measured at 2k ctx).

    ``flash`` (static, from :func:`flash_decode_wanted`) routes the
    single-token path into the fused pallas kernel
    (ops/flash_attention.py flash_decode_attention), which skips cache
    blocks past ``pos`` entirely and — given ``k_scale``/``v_scale`` —
    reads the int8 cache directly, dequantizing in VMEM."""
    B, Q, H, Dh = q.shape
    KV = k.shape[1]
    T = k.shape[2]
    g = H // KV
    if flash and pos is not None and Q == 1:
        from dlrover_tpu.ops.flash_attention import flash_decode_attention

        qg = q.reshape(B, KV, g, Dh)
        out = flash_decode_attention(
            qg, k, v, pos, scale=scale, block_k=_DECODE_BLOCK_K,
            k_scale=k_scale, v_scale=v_scale,
        )
        return out.reshape(B, Q, H * Dh)
    qg = q.reshape(B, Q, KV, g, Dh)
    scores = jnp.einsum(
        "bqkgd,bktd->bkgqt", qg, k, preferred_element_type=jnp.float32
    ) * scale
    # mask (B,1,Q,T) → broadcast over the (KV, g) head axes
    scores = jnp.where(mask[:, :, None], scores, jnp.float32(-1e30))
    att = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgqt,bktd->bqkgd", att.astype(v.dtype), v
    )
    return out.reshape(B, Q, H * Dh)


def planned_cache_len(total: int, quantize_cache: bool,
                      max_len: Optional[int] = None) -> Tuple[int, bool]:
    """(allocated cache length, will-the-fused-kernel-run) for a
    :func:`generate` call with these arguments — the ONE sizing/routing
    decision, shared with the bench's HBM-roof accounting so a reported
    %-of-roof always describes the cache actually allocated."""
    if max_len is None:
        rounded = -(-total // _DECODE_BLOCK_K) * _DECODE_BLOCK_K
        flash = flash_decode_wanted(rounded, quantize_cache,
                                    live_len=total)
        return (rounded if flash else total), flash
    return max_len, flash_decode_wanted(max_len, quantize_cache,
                                        live_len=total)


def prefill(params: Dict, tokens, config,
            max_len: int, quantize: bool = False) -> Tuple[jnp.ndarray, Dict]:
    """Run the prompt ``tokens`` (B, P) through the model in one batched
    pass, building a ``max_len``-slot cache (int8 when ``quantize``).
    Returns (logits for the next token (B, V), cache)."""
    c = config
    B, P = tokens.shape
    T = max_len
    if P > T:
        raise ValueError(f"prompt length {P} exceeds cache length {T}")
    x = params["tok_embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(P)[None, :], (B, P))
    causal = (
        jnp.arange(P)[None, None, :, None] >= jnp.arange(P)[None, None, None, :]
    )
    scale = c.head_dim ** -0.5
    # long prompts take the pallas flash kernel (the same one training
    # uses): the dense einsum materializes the (B, H, P, P) score tensor
    # — at a 2k prompt that is ~2 GB of f32 written+read per layer, a
    # pure TTFT tax the blockwise kernel never pays (measured 0.40 s →
    # 0.16 s at 2k × batch 8 on v5e). Same override knob as training:
    # config.use_flash_attention (None = auto by backend).
    uf = getattr(c, "use_flash_attention", None)
    use_flash = (
        (jax.default_backend() == "tpu" if uf is None else uf)
        and P >= 256
    )
    if not use_flash:
        log_once(
            "prefill attention: dense XLA path (use_flash_attention=%s, "
            "default backend %r, prompt length %s; the kernel needs tpu "
            "or an explicit True, and >= 256 tokens)",
            uf, jax.default_backend(), P,
        )

    def layer_fn(h, layer):
        xn = _rms_norm(h, layer["attn_norm"], c.norm_eps)
        q = _rope(_split_heads(xn @ layer["wq"], c.n_heads, c.head_dim),
                  positions, c.rope_theta)
        k = _rope(_split_heads(xn @ layer["wk"], c.n_kv_heads, c.head_dim),
                  positions, c.rope_theta)
        v = _split_heads(xn @ layer["wv"], c.n_kv_heads, c.head_dim)
        # head-major for the attend AND the cache (one transpose here,
        # at MXU-shaped prefill cost — decode reads it every step)
        k = jnp.swapaxes(k, 1, 2)                    # (B, KV, P, Dh)
        v = jnp.swapaxes(v, 1, 2)
        if use_flash:
            from dlrover_tpu.ops.flash_attention import (
                flash_attention,
                repeat_kv,
            )

            kr, vr = repeat_kv(k, v, c.n_heads // c.n_kv_heads)
            out = flash_attention(
                jnp.swapaxes(q, 1, 2), kr, vr, causal=True, scale=scale,
            )
            out = jnp.swapaxes(out, 1, 2).reshape(
                B, P, c.n_heads * c.head_dim)
        else:
            out = _attend(q, k, v, causal, scale)
        h = h + out @ layer["wo"]
        h = h + _ffn(_rms_norm(h, layer["ffn_norm"], c.norm_eps), layer, c)
        return h, (k, v)

    x, (ks, vs) = jax.lax.scan(layer_fn, x, params["layers"])
    # ks/vs: (L, B, KV, P, Dh); pad the time axis up to the cache length
    # and split into the per-layer tuples decode_step updates in place
    # (the split is L static slices — a one-time prefill cost, vs the
    # per-step copies a stacked cache costs the decode loop)
    pad = [(0, 0), (0, 0), (0, 0), (0, T - P), (0, 0)]

    def split(stacked):
        return tuple(stacked[i] for i in range(c.n_layers))

    if quantize:
        kq, ksc = _quantize(ks)
        vq, vsc = _quantize(vs)
        cache = {
            "k": split(jnp.pad(kq, pad)),
            "v": split(jnp.pad(vq, pad)),
            "k_scale": split(jnp.pad(ksc, pad[:-1])),
            "v_scale": split(jnp.pad(vsc, pad[:-1])),
            "pos": jnp.int32(P),
        }
    else:
        cache = {
            "k": split(jnp.pad(ks, pad).astype(c.dtype)),
            "v": split(jnp.pad(vs, pad).astype(c.dtype)),
            "pos": jnp.int32(P),
        }
    x = _rms_norm(x, params["final_norm"], c.norm_eps)
    logits = (x[:, -1] @ params["lm_head"]).astype(jnp.float32)
    return logits, cache


def decode_step(params: Dict, token, cache: Dict,
                config, flash: Optional[bool] = None) -> Tuple[jnp.ndarray, Dict]:
    """One autoregressive step: ``token`` (B,) int32 at position
    ``cache['pos']`` → (next-token logits (B, V), updated cache).

    ``flash`` routes the attend through the fused pallas decode kernel
    (must be a static Python bool; None = :func:`flash_decode_wanted`
    auto policy)."""
    c = config
    B = token.shape[0]
    T = cache["k"][0].shape[2]  # per-layer head-major (B, KV, T, Dh)
    pos = cache["pos"]
    x = params["tok_embed"][token][:, None, :]          # (B, 1, D)
    positions = jnp.broadcast_to(pos[None, None], (B, 1))
    # attend to [0, pos] only (the cache beyond is zeros/garbage)
    mask = (jnp.arange(T)[None, None, None, :] <= pos)
    scale = c.head_dim ** -0.5

    quantized = "k_scale" in cache
    if flash is None:
        flash = flash_decode_wanted(T, quantized)
    # one body for both layouts: each layer's cache buffers are threaded
    # as a dict keyed by this list, so adding a cache field means adding
    # one key — the structure and rebuild stay single-sited
    cache_keys = ["k", "v"] + (["k_scale", "v_scale"] if quantized else [])
    bufs = {name: list(cache[name]) for name in cache_keys}

    # UNROLLED layer loop, one buffer per layer: each
    # dynamic_update_slice's operand dies at the update, which is the
    # form XLA's in-place-DUS optimization matches inside the token
    # loop's while carry — the cache is written one row per layer with
    # NO copy traffic. The r3 layer scan threaded per-layer slices
    # through scan xs/ys and re-stacked ~2 full cache copies per step
    # (~13 ms/step at 2k ctx on v5e); carrying one stacked (L, …) buffer
    # through a layer scan is worse still (XLA copies the whole stack at
    # every layer's traced-index DUS: 36.6 ms/step measured). Unrolled:
    # 4.5 ms/step — 78% of the HBM roof. Params stay layer-stacked
    # (static reads are free); only the cache is per-layer.
    h = x
    for li in range(c.n_layers):
        layer = jax.tree.map(lambda w, li=li: w[li], params["layers"])
        xn = _rms_norm(h, layer["attn_norm"], c.norm_eps)
        q = _rope(_split_heads(xn @ layer["wq"], c.n_heads, c.head_dim),
                  positions, c.rope_theta)
        k_new = _rope(
            _split_heads(xn @ layer["wk"], c.n_kv_heads, c.head_dim),
            positions, c.rope_theta,
        )
        v_new = _split_heads(xn @ layer["wv"], c.n_kv_heads, c.head_dim)
        k_new = jnp.swapaxes(k_new, 1, 2)            # (B, KV, 1, Dh)
        v_new = jnp.swapaxes(v_new, 1, 2)
        if quantized:
            kq, ksc = _quantize(k_new)
            vq, vsc = _quantize(v_new)
            writes = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
        else:
            writes = {
                "k": k_new.astype(bufs["k"][li].dtype),
                "v": v_new.astype(bufs["v"][li].dtype),
            }
        for name, val in writes.items():
            # time is axis 2 in the head-major layout (values (B,KV,1,Dh)
            # / scales (B,KV,1))
            bufs[name][li] = jax.lax.dynamic_update_slice(
                bufs[name][li], val, (0, 0, pos) + (0,) * (val.ndim - 3)
            )
        if quantized and flash:
            # fused dequant-attend: the int8 cache goes straight into the
            # kernel, no bf16 materialization
            out = _attend(
                q, bufs["k"][li], bufs["v"][li], mask, scale, pos=pos,
                flash=True, k_scale=bufs["k_scale"][li],
                v_scale=bufs["v_scale"][li],
            )
        elif quantized:
            k_read = _dequantize(bufs["k"][li], bufs["k_scale"][li],
                                 c.dtype)
            v_read = _dequantize(bufs["v"][li], bufs["v_scale"][li],
                                 c.dtype)
            out = _attend(q, k_read, v_read, mask, scale, pos=None)
        else:
            out = _attend(q, bufs["k"][li], bufs["v"][li], mask, scale,
                          pos=pos, flash=flash)
        h = h + out @ layer["wo"]
        h = h + _ffn(_rms_norm(h, layer["ffn_norm"], c.norm_eps), layer, c)

    x = h
    cache = {name: tuple(bufs[name]) for name in cache_keys}
    cache["pos"] = pos + 1
    x = _rms_norm(x, params["final_norm"], c.norm_eps)
    logits = (x[:, 0] @ params["lm_head"]).astype(jnp.float32)
    return logits, cache


def decode_window(params: Dict, tokens, cache: Dict,
                  config) -> Tuple[jnp.ndarray, Dict]:
    """One batched multi-token step: ``tokens`` (B, K) occupy positions
    ``pos .. pos+K-1`` → (logits (B, K, V) — row ``i`` is the next-token
    distribution AFTER ``tokens[:, i]`` — and the cache with ``pos + K``).

    This is the speculative-decoding VERIFY leg: the target model scores
    all K drafted tokens in one forward instead of K sequential steps.
    The window's k/v rows are written before the attend (causal mask
    within the window), so an accepting caller keeps them for free; a
    rejecting caller rewinds ``cache['pos']`` — rows past ``pos`` are
    exactly the garbage the step mask already never reveals (the same
    argument as the zero-initialized cache)."""
    c = config
    B, K = tokens.shape
    T = cache["k"][0].shape[2]
    pos = cache["pos"]
    x = params["tok_embed"][tokens]                      # (B, K, D)
    positions = jnp.broadcast_to((pos + jnp.arange(K))[None], (B, K))
    # query i sits at absolute position pos+i: attend [0, pos+i]
    mask = (
        jnp.arange(T)[None, None, None, :]
        <= (pos + jnp.arange(K))[None, None, :, None]
    )
    scale = c.head_dim ** -0.5

    quantized = "k_scale" in cache
    cache_keys = ["k", "v"] + (["k_scale", "v_scale"] if quantized else [])
    bufs = {name: list(cache[name]) for name in cache_keys}

    h = x
    for li in range(c.n_layers):
        layer = jax.tree.map(lambda w, li=li: w[li], params["layers"])
        xn = _rms_norm(h, layer["attn_norm"], c.norm_eps)
        q = _rope(_split_heads(xn @ layer["wq"], c.n_heads, c.head_dim),
                  positions, c.rope_theta)
        k_new = _rope(
            _split_heads(xn @ layer["wk"], c.n_kv_heads, c.head_dim),
            positions, c.rope_theta,
        )
        v_new = _split_heads(xn @ layer["wv"], c.n_kv_heads, c.head_dim)
        k_new = jnp.swapaxes(k_new, 1, 2)                # (B, KV, K, Dh)
        v_new = jnp.swapaxes(v_new, 1, 2)
        if quantized:
            kq, ksc = _quantize(k_new)
            vq, vsc = _quantize(v_new)
            writes = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
        else:
            writes = {
                "k": k_new.astype(bufs["k"][li].dtype),
                "v": v_new.astype(bufs["v"][li].dtype),
            }
        for name, val in writes.items():
            bufs[name][li] = jax.lax.dynamic_update_slice(
                bufs[name][li], val, (0, 0, pos) + (0,) * (val.ndim - 3)
            )
        if quantized:
            k_read = _dequantize(bufs["k"][li], bufs["k_scale"][li],
                                 c.dtype)
            v_read = _dequantize(bufs["v"][li], bufs["v_scale"][li],
                                 c.dtype)
            out = _attend(q, k_read, v_read, mask, scale, pos=None)
        else:
            out = _attend(q, bufs["k"][li], bufs["v"][li], mask, scale)
        h = h + out @ layer["wo"]
        h = h + _ffn(_rms_norm(h, layer["ffn_norm"], c.norm_eps), layer, c)

    cache = {name: tuple(bufs[name]) for name in cache_keys}
    cache["pos"] = pos + K
    x = _rms_norm(h, params["final_norm"], c.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)  # (B, K, V)
    return logits, cache


def sample_token(logits, key, temperature: float = 1.0, top_k: int = 0):
    """f32 categorical sampling; temperature 0 → greedy; top_k > 0 keeps
    only the k best logits (both static Python values).

    With top_k the categorical runs over the (B, k) TOP-K VALUES and the
    choice maps back through the indices — not over a masked (B, V)
    tensor: the full-vocab gumbel+reduction was ~0.6 ms/step at V=32k
    (~12% of a 2k-ctx decode step on v5e), the k-wide one is free."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if top_k > 0:
        vals, idx = jax.lax.top_k(logits, top_k)        # (..., k)
        choice = jax.random.categorical(
            key, vals / temperature, axis=-1
        )
        return jnp.take_along_axis(
            idx, choice[..., None], axis=-1
        )[..., 0].astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1
    ).astype(jnp.int32)


def generate(params: Dict, prompt, config, key,
             max_new_tokens: int, temperature: float = 1.0,
             top_k: int = 0, max_len: Optional[int] = None,
             quantize_cache: bool = False):
    """Sample ``max_new_tokens`` continuations of ``prompt`` (B, P).
    Returns (B, P + max_new_tokens) int32. One compiled program: batched
    prefill + a ``lax.scan`` of cached decode steps."""
    B, P = prompt.shape
    total = P + max_new_tokens
    # a right-sized cache keeps per-step KV traffic minimal on the einsum
    # path; the fused kernel needs a block-multiple length but skips the
    # padded blocks at ~zero bandwidth, so the cache is rounded up only
    # when the kernel will actually run — planned_cache_len decides BOTH
    # the size and the routing, so they cannot disagree
    max_len, flash = planned_cache_len(total, quantize_cache, max_len)
    if total > max_len:
        # dynamic_update_slice would silently clamp writes to the last
        # slot and corrupt the tail — refuse instead
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"cache length {max_len}"
        )
    keys = jax.random.split(key, max_new_tokens)
    logits, cache = prefill(
        params, prompt, config, max_len, quantize=quantize_cache
    )

    def step(carry, step_key):
        logits, cache = carry
        nxt = sample_token(logits, step_key, temperature, top_k)
        logits, cache = decode_step(params, nxt, cache, config, flash=flash)
        return (logits, cache), nxt

    if max_new_tokens > 1:
        # the token sampled from the final carry needs no decode step —
        # scanning all max_new_tokens would waste one full forward
        (logits, cache), toks = jax.lax.scan(
            step, (logits, cache), keys[:-1]
        )
        toks = toks.T
    else:
        toks = jnp.zeros((B, 0), jnp.int32)
    last = sample_token(logits, keys[-1], temperature, top_k)
    return jnp.concatenate([prompt, toks, last[:, None]], axis=1)
