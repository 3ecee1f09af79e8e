"""Multi-slot batched decode engine for serving replicas.

``models/decode.py`` owns the single-sequence path (one scalar ``pos``,
whole-batch prefill→decode). Serving needs sequences at DIFFERENT
positions in one batch — continuous batching — so this engine keeps a
per-SLOT position vector over the same head-major per-layer cache layout
and splits prefill in two:

- :meth:`BatchDecodeEngine.prefill_rows` is a PURE function of the
  prompt (no engine state touched): it runs the bucket-padded prompt
  through a single-sequence forward and returns the per-layer k/v rows
  plus the first generated token. Pure means the batcher's prefill
  workers can run it CONCURRENTLY with the decode loop — the real
  prefill/decode overlap, not a scheduling trick.
- :meth:`BatchDecodeEngine.insert` is the cheap, decode-thread-only
  commit: one ``dynamic_update_slice`` of the precomputed rows into the
  slot's cache rows and a ``pos[slot] = real_len`` write.

Compile discipline (the batcher's "never recompiles mid-bucket"
invariant): prompts are right-padded to their admission bucket's length,
so prefill traces once per BUCKET, and the decode step traces exactly
once (fixed ``(slots,)`` shapes). ``compile_count`` tracks distinct
traced shapes for the invariant test.

Padding correctness: the pad rows write garbage k/v beyond ``real_len``,
but the step mask is ``arange(T) <= pos`` and every cell at ``pos`` is
written before it is attended — garbage is always overwritten before it
becomes visible (same argument as decode.py's zero-initialized cache).

Greedy sampling only: serving decode must be a pure function of the
prompt so the router can replay a request on another replica after a
death (idempotent retry). Temperature sampling would need the request to
carry its PRNG key to stay replayable — headroom, not needed here.

A :class:`ToyEngine` with the same interface (deterministic integer
recurrence, no jax) backs the fast batcher/router unit tests.
"""

import threading
from dataclasses import dataclass
from typing import Any, List, Sequence

from dlrover_tpu.common.constants import MetricLabel
from dlrover_tpu.common.log import logger
from dlrover_tpu.observability.compile_watch import get_watcher
from dlrover_tpu.observability.memory import get_accountant


def _shape_sig(key):
    """Map an engine shape key onto a (fn, structured dims) compile
    signature — the dims are what lets the watcher attribute a storm to
    its varying dimension (ragged buckets → seq_len)."""
    name = key[0]
    dims = {}
    if name in ("prefill", "insert") and len(key) > 1:
        dims["bucket"] = key[1]
    elif name == "prefill_sfx" and len(key) > 2:
        dims["bucket"], dims["prefix_len"] = key[1], key[2]
    return f"engine.{name}", dims


@dataclass
class PrefillResult:
    """Output of a pure prefill: what :meth:`insert` commits to a slot."""

    first_token: int
    real_len: int
    bucket_len: int
    # backend payload: (L, KV, P, Dh) k/v stacks for the jax engine, the
    # recurrence seed for the toy engine
    payload: Any = None


class ToyEngine:
    """Deterministic stand-in engine (no jax): token ``i`` of a sequence
    is a fixed integer function of (prompt, i), so two replicas given the
    same request produce identical outputs — the property idempotent
    retry rests on — while a batcher step costs microseconds."""

    def __init__(self, slots: int = 4, vocab: int = 97,
                 cache_len: int = 1024, prefill_delay_s: float = 0.0,
                 step_delay_s: float = 0.0):
        self.slots = slots
        self.cache_len = cache_len
        self._vocab = vocab
        self._prefill_delay_s = prefill_delay_s
        self._step_delay_s = step_delay_s
        self._seeds = [0] * slots
        self._counts = [0] * slots
        self._shapes_lock = threading.Lock()
        self._shapes = set()
        # nominal KV residency (16 bytes/token, the prefix_entry rate) so
        # toy-backed fleet tests exercise the same ledger as the jax path
        get_accountant().register(
            MetricLabel.MEM_KV_CACHE, f"toy_engine/{id(self):x}/kv",
            16 * slots * cache_len)

    @property
    def compile_count(self) -> int:
        with self._shapes_lock:
            return len(self._shapes)

    def _note_shape(self, key) -> None:
        with self._shapes_lock:
            self._shapes.add(key)
        fn, dims = _shape_sig(key)
        get_watcher().note(fn, **dims)

    @staticmethod
    def _seed(prompt: Sequence[int]) -> int:
        return (sum(prompt) * 1000003 + len(prompt)) & 0x7FFFFFFF

    def _token(self, seed: int, i: int) -> int:
        return (seed * 31 + 7 + i * 17) % self._vocab

    def prefill_rows(self, prompt: Sequence[int],
                     bucket_len: int) -> PrefillResult:
        if self._prefill_delay_s:
            import time

            time.sleep(self._prefill_delay_s)  # simulated prefill work
        self._note_shape(("prefill", bucket_len))
        seed = self._seed(prompt)
        return PrefillResult(
            first_token=self._token(seed, 0),
            real_len=len(prompt),
            bucket_len=bucket_len,
            payload=seed,
        )

    def prefix_entry(self, result: PrefillResult):
        """(trie payload, nominal byte cost) — the toy recurrence carries
        no k/v rows, so the payload is just the seed and the cost a
        per-token stand-in that still exercises the cache's byte budget."""
        return result.payload, 16 * result.real_len

    def prefill_with_prefix(self, prompt: Sequence[int], bucket_len: int,
                            entry, m: int) -> PrefillResult:
        """Same outputs as :meth:`prefill_rows` (the toy seed depends on
        the FULL prompt), with the simulated prefill cost scaled to the
        suffix fraction — what the prefix cache actually saves."""
        del entry
        if not 1 <= m < len(prompt):
            raise ValueError(f"matched length {m} outside [1, prompt)")
        if self._prefill_delay_s:
            import time

            time.sleep(
                self._prefill_delay_s * (len(prompt) - m) / len(prompt))
        self._note_shape(("prefill_sfx", bucket_len, m))
        seed = self._seed(prompt)
        return PrefillResult(
            first_token=self._token(seed, 0),
            real_len=len(prompt),
            bucket_len=bucket_len,
            payload=seed,
        )

    def insert(self, result: PrefillResult, slot: int) -> int:
        self._seeds[slot] = result.payload
        self._counts[slot] = 1
        return result.first_token

    def step(self, tokens: Sequence[int],
             active: Sequence[bool]) -> List[int]:
        del tokens  # the recurrence carries its own state
        if self._step_delay_s:
            import time

            time.sleep(self._step_delay_s)  # simulated decode work
        self._note_shape(("step",))
        out = []
        for s in range(self.slots):
            if active[s]:
                i = self._counts[s]
                self._counts[s] += 1
                out.append(self._token(self._seeds[s], i))
            else:
                out.append(0)
        return out


class BatchDecodeEngine:
    """Jax engine: per-layer head-major ``(S, KV, T, Dh)`` cache buffers
    (the decode.py layout, batch axis = slots) + a ``(S,)`` position
    vector. Greedy decode; CPU/TPU-portable (no pallas dependency — the
    einsum attend path, see ``flash_decode_wanted`` for when the fused
    kernel would take over on TPU).

    ``quantize=True`` switches the cache to decode.py's int8 layout —
    int8 k/v plus per-vector f32 absmax scales (``(S, KV, T)``, one per
    cached vector) — with the SAME ``_quantize``/``_dequantize`` math as
    the stock quantized path, so the batched engine stays token-exact
    against ``decode.generate(quantize_cache=True)``. The cache is the
    serving memory term that scales with slots × context, so int8 halves
    it; on CPU the attend reads ~3× fewer cache bytes (int8 + one f32
    scale per vector vs f32 vectors) and XLA fuses the dequant into the
    einsum loop, measured ≥1.5× bf16 step throughput at 1k context
    (bench ``serving`` section keeps the honest pair). The fused-kernel
    POLICY (``flash_decode_wanted``) routes here exactly as in
    ``decode_step``; the kernel itself takes a scalar ``pos``, so the
    batched step engages it only when every active slot sits at the same
    position (lockstep generation — the RL rollout shape) and falls back
    to the XLA attend otherwise."""

    def __init__(self, params, config, slots: int = 4,
                 cache_len: int = 64, quantize: bool = False):
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.models.decode import flash_decode_wanted

        self.slots = slots
        self.cache_len = cache_len
        self.quantize = quantize
        self._params = params
        self._config = config
        c = config
        shape = (slots, c.n_kv_heads, cache_len, c.head_dim)
        if quantize:
            self._k = tuple(
                jnp.zeros(shape, jnp.int8) for _ in range(c.n_layers))
            self._v = tuple(
                jnp.zeros(shape, jnp.int8) for _ in range(c.n_layers))
            self._ks = tuple(
                jnp.zeros(shape[:-1], jnp.float32)
                for _ in range(c.n_layers))
            self._vs = tuple(
                jnp.zeros(shape[:-1], jnp.float32)
                for _ in range(c.n_layers))
        else:
            self._k = tuple(
                jnp.zeros(shape, c.dtype) for _ in range(c.n_layers))
            self._v = tuple(
                jnp.zeros(shape, c.dtype) for _ in range(c.n_layers))
            # zero-size placeholders keep one jit signature for both
            # layouts (static branch on ``self.quantize`` inside)
            self._ks = tuple(
                jnp.zeros((0,), jnp.float32) for _ in range(c.n_layers))
            self._vs = tuple(
                jnp.zeros((0,), jnp.float32) for _ in range(c.n_layers))
        self._pos = jnp.zeros((slots,), jnp.int32)
        # the decode.py routing policy, decided once per engine (static):
        # on TPU with a block-multiple cache the attend takes the fused
        # kernel when the active slots are in lockstep
        self._flash = flash_decode_wanted(cache_len, quantize)
        # public for equality tests against the stock decode.py path
        self.params = params
        self.config = config
        self._shapes_lock = threading.Lock()
        self._shapes = set()
        self._prefill_jit = jax.jit(self._prefill_fn)
        self._insert_jit = jax.jit(self._insert_fn)
        self._step_jit = jax.jit(self._step_fn)
        # chunked prefix-prefill traces per (bucket, matched-len) pair;
        # matched lengths are block-quantized by the prefix cache so the
        # trace count stays bounded
        self._sfx_jit = jax.jit(self._prefill_suffix_fn)
        # claim the slot caches in the device-memory ledger — the serving
        # term that scales with slots × context, exactly what the
        # max-slots ceiling projection divides headroom by
        get_accountant().register(
            MetricLabel.MEM_KV_CACHE, f"engine/{id(self):x}/kv",
            self.kv_cache_bytes())

    def kv_cache_bytes(self) -> int:
        """Actual resident bytes of the slot caches (k/v buffers plus the
        quantization scales) — the accountant's measured counterpart to
        memory.kv_bytes_per_slot_theoretical."""
        return int(sum(
            b.nbytes
            for bufs in (self._k, self._v, self._ks, self._vs)
            for b in bufs
        ))

    @property
    def kv_bytes_per_slot(self) -> int:
        return self.kv_cache_bytes() // self.slots

    @property
    def compile_count(self) -> int:
        with self._shapes_lock:
            return len(self._shapes)

    def _note_shape(self, key) -> None:
        """Track the shape locally (compile_count invariant) and note
        its signature with the process watcher."""
        with self._shapes_lock:
            if key not in self._shapes:
                self._shapes.add(key)
                logger.info("serving engine traces %s", key)
        fn, dims = _shape_sig(key)
        get_watcher().note(fn, **dims)

    # -- pure prefill (prefill-worker threads) -----------------------------

    def _prefill_fn(self, params, tokens, real_len):
        """Single-sequence bucket-padded forward → (first greedy token,
        (L, KV, P, Dh) k stack, v stack). Pure: touches no engine state."""
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.models.decode import _attend, _ffn, _split_heads
        from dlrover_tpu.models.llama import _rms_norm, _rope

        c = self._config
        P = tokens.shape[0]
        x = params["tok_embed"][tokens][None]           # (1, P, D)
        positions = jnp.arange(P)[None]
        # causal over the padded length: the logits row at real_len-1
        # never attends a pad key (pads sit at indices >= real_len)
        causal = (
            jnp.arange(P)[None, None, :, None]
            >= jnp.arange(P)[None, None, None, :]
        )
        scale = c.head_dim ** -0.5

        def layer_fn(h, layer):
            xn = _rms_norm(h, layer["attn_norm"], c.norm_eps)
            q = _rope(_split_heads(xn @ layer["wq"], c.n_heads, c.head_dim),
                      positions, c.rope_theta)
            k = _rope(
                _split_heads(xn @ layer["wk"], c.n_kv_heads, c.head_dim),
                positions, c.rope_theta,
            )
            v = _split_heads(xn @ layer["wv"], c.n_kv_heads, c.head_dim)
            k = jnp.swapaxes(k, 1, 2)                   # (1, KV, P, Dh)
            v = jnp.swapaxes(v, 1, 2)
            out = _attend(q, k, v, causal, scale)
            h = h + out @ layer["wo"]
            h = h + _ffn(_rms_norm(h, layer["ffn_norm"], c.norm_eps),
                         layer, c)
            return h, (k, v)

        x, (ks, vs) = jax.lax.scan(layer_fn, x, params["layers"])
        x = _rms_norm(x, params["final_norm"], c.norm_eps)
        # the next-token logits live at the LAST REAL position, not the
        # padded tail
        h_last = jax.lax.dynamic_slice_in_dim(x[0], real_len - 1, 1)[0]
        logits = (h_last @ params["lm_head"]).astype(jnp.float32)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return first, ks[:, 0].astype(c.dtype), vs[:, 0].astype(c.dtype)

    def prefill_rows(self, prompt: Sequence[int],
                     bucket_len: int) -> PrefillResult:
        import jax.numpy as jnp

        if len(prompt) > bucket_len:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds bucket {bucket_len}")
        if bucket_len > self.cache_len:
            raise ValueError(
                f"bucket {bucket_len} exceeds cache length {self.cache_len}")
        padded = list(prompt) + [0] * (bucket_len - len(prompt))
        self._note_shape(("prefill", bucket_len))
        first, ks, vs = self._prefill_jit(
            self._params,
            jnp.asarray(padded, jnp.int32),
            jnp.int32(len(prompt)),
        )
        return PrefillResult(
            first_token=int(first),
            real_len=len(prompt),
            bucket_len=bucket_len,
            payload=(ks, vs),
        )

    # -- prefix-cache surface (serving/prefix_cache.py) --------------------

    def prefix_entry(self, result: PrefillResult):
        """(trie payload, byte cost) for a completed prefill — the k/v
        row stacks themselves (jax arrays are immutable, so the trie's
        reference stays valid however the slot cache evolves)."""
        ks, vs = result.payload
        return result.payload, int(ks.nbytes + vs.nbytes)

    def _prefill_suffix_fn(self, params, tokens_sfx, real_len,
                           pre_k, pre_v):
        """Chunked prefill: positions ``[m, P)`` forward against cached
        prefix rows ``pre_k``/``pre_v`` (L, KV, m, Dh). Returns the SAME
        (first token, full (L, KV, P, Dh) stacks) a cold prefill of the
        whole bucket produces: suffix queries attend the concatenated
        [cached; new] keys under the identical causal mask rows, so every
        computed row and the first-token argmax match the cold path."""
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.models.decode import _attend, _ffn, _split_heads
        from dlrover_tpu.models.llama import _rms_norm, _rope

        c = self._config
        S = tokens_sfx.shape[0]
        m = pre_k.shape[2]
        P = m + S
        x = params["tok_embed"][tokens_sfx][None]       # (1, S, D)
        positions = (m + jnp.arange(S))[None]
        # rows m..P-1 of the full (P, P) causal mask
        mask = (
            (m + jnp.arange(S))[None, None, :, None]
            >= jnp.arange(P)[None, None, None, :]
        )
        scale = c.head_dim ** -0.5

        def layer_fn(h, xs):
            layer, pk, pv = xs
            xn = _rms_norm(h, layer["attn_norm"], c.norm_eps)
            q = _rope(_split_heads(xn @ layer["wq"], c.n_heads, c.head_dim),
                      positions, c.rope_theta)
            k = _rope(
                _split_heads(xn @ layer["wk"], c.n_kv_heads, c.head_dim),
                positions, c.rope_theta,
            )
            v = _split_heads(xn @ layer["wv"], c.n_kv_heads, c.head_dim)
            k = jnp.swapaxes(k, 1, 2)                   # (1, KV, S, Dh)
            v = jnp.swapaxes(v, 1, 2)
            k_full = jnp.concatenate([pk[None], k], axis=2)
            v_full = jnp.concatenate([pv[None], v], axis=2)
            out = _attend(q, k_full, v_full, mask, scale)
            h = h + out @ layer["wo"]
            h = h + _ffn(_rms_norm(h, layer["ffn_norm"], c.norm_eps),
                         layer, c)
            return h, (k_full[0], v_full[0])

        x, (ks, vs) = jax.lax.scan(
            layer_fn, x, (params["layers"], pre_k, pre_v))
        x = _rms_norm(x, params["final_norm"], c.norm_eps)
        h_last = jax.lax.dynamic_slice_in_dim(x[0], real_len - 1 - m, 1)[0]
        logits = (h_last @ params["lm_head"]).astype(jnp.float32)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return first, ks.astype(c.dtype), vs.astype(c.dtype)

    def prefill_with_prefix(self, prompt: Sequence[int], bucket_len: int,
                            entry, m: int) -> PrefillResult:
        """Prefill reusing ``m`` cached rows (``entry`` = the trie's
        (ks, vs) stacks for a prompt sharing our first ``m`` tokens).
        Only positions ``[m, bucket_len)`` are computed — the prefix-cache
        win. Requires ``1 <= m < len(prompt)``."""
        import jax.numpy as jnp

        if not 1 <= m < len(prompt):
            raise ValueError(f"matched length {m} outside [1, prompt)")
        if len(prompt) > bucket_len or bucket_len > self.cache_len:
            raise ValueError(
                f"prompt {len(prompt)} / bucket {bucket_len} exceed "
                f"cache length {self.cache_len}")
        pre_ks, pre_vs = entry
        padded = list(prompt) + [0] * (bucket_len - len(prompt))
        self._note_shape(("prefill_sfx", bucket_len, m))
        first, ks, vs = self._sfx_jit(
            self._params,
            jnp.asarray(padded[m:], jnp.int32),
            jnp.int32(len(prompt)),
            pre_ks[:, :, :m],
            pre_vs[:, :, :m],
        )
        return PrefillResult(
            first_token=int(first),
            real_len=len(prompt),
            bucket_len=bucket_len,
            payload=(ks, vs),
        )

    # -- decode-thread-only state commits ----------------------------------

    def _insert_fn(self, k_bufs, v_bufs, ks_bufs, vs_bufs, pos, ks, vs,
                   slot, real_len):
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.models.decode import _quantize

        new_k, new_v = [], []
        new_ks, new_vs = list(ks_bufs), list(vs_bufs)
        for li in range(self._config.n_layers):
            rows_k, rows_v = ks[li], vs[li]
            if self.quantize:
                # same per-vector absmax math as decode.prefill's
                # quantize-then-pad: rows within [0, real_len) come out
                # bitwise identical, and the padded-garbage rows beyond
                # stay masked exactly like the bf16 path's
                rows_k, sc_k = _quantize(rows_k)
                rows_v, sc_v = _quantize(rows_v)
                new_ks[li] = jax.lax.dynamic_update_slice(
                    ks_bufs[li], sc_k[None], (slot, 0, 0))
                new_vs[li] = jax.lax.dynamic_update_slice(
                    vs_bufs[li], sc_v[None], (slot, 0, 0))
            # write the (KV, P, Dh) rows at batch row ``slot``; the stale
            # tail beyond P from a previous occupant stays masked until
            # overwritten (mask <= pos, and the cell at pos is written
            # before it is read each step)
            new_k.append(jax.lax.dynamic_update_slice(
                k_bufs[li], rows_k[None], (slot, 0, 0, 0)))
            new_v.append(jax.lax.dynamic_update_slice(
                v_bufs[li], rows_v[None], (slot, 0, 0, 0)))
        pos = pos.at[slot].set(real_len.astype(jnp.int32))
        return (tuple(new_k), tuple(new_v), tuple(new_ks), tuple(new_vs),
                pos)

    def insert(self, result: PrefillResult, slot: int) -> int:
        import jax.numpy as jnp

        ks, vs = result.payload
        self._note_shape(("insert", result.bucket_len))
        self._k, self._v, self._ks, self._vs, self._pos = \
            self._insert_jit(
                self._k, self._v, self._ks, self._vs, self._pos, ks, vs,
                jnp.int32(slot), jnp.int32(result.real_len),
            )
        return result.first_token

    def _step_fn(self, params, k_bufs, v_bufs, ks_bufs, vs_bufs, pos,
                 tokens, active):
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.models.decode import (
            _attend,
            _dequantize,
            _ffn,
            _quantize,
            _split_heads,
        )
        from dlrover_tpu.models.llama import _rms_norm, _rope

        c = self._config
        T = self.cache_len
        x = params["tok_embed"][tokens][:, None, :]     # (S, 1, D)
        positions = pos[:, None]                        # per-slot position
        mask = (
            jnp.arange(T)[None, None, None, :]
            <= pos[:, None, None, None]
        )
        scale = c.head_dim ** -0.5
        if self._flash:
            # the fused kernel takes one SCALAR pos — usable only when
            # every active slot sits at the same position (lockstep
            # generation). Decided per step with a lax.cond; inactive
            # rows ride along and their outputs are discarded upstream.
            pos0 = jnp.max(jnp.where(active, pos, 0))
            lockstep = jnp.all(
                jnp.where(active, pos, pos0) == pos0) & jnp.any(active)

        def row_write(buf_row, val_row, p):
            # (KV, T, Dh) ← (KV, 1, Dh) at this row's own position
            # (scales: (KV, T) ← (KV, 1))
            idx = (0, p) + (0,) * (val_row.ndim - 2)
            return jax.lax.dynamic_update_slice(buf_row, val_row, idx)

        k_bufs, v_bufs = list(k_bufs), list(v_bufs)
        ks_bufs, vs_bufs = list(ks_bufs), list(vs_bufs)
        h = x
        # unrolled layer loop, per-layer buffers: the decode.py in-place-
        # DUS shape, now with a vmap over slots for the per-row positions
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
            k_new = jnp.swapaxes(k_new, 1, 2)           # (S, KV, 1, Dh)
            v_new = jnp.swapaxes(v_new, 1, 2)
            # inactive rows write garbage at their frozen pos — harmless:
            # that cell is rewritten (insert or this write) before any
            # mask ever reveals it
            if self.quantize:
                # decode_step's per-step math exactly: per-vector absmax
                # over the (S, KV, 1, Dh) new rows → (S, KV, 1) scales
                kq, ksc = _quantize(k_new)
                vq, vsc = _quantize(v_new)
                k_bufs[li] = jax.vmap(row_write)(k_bufs[li], kq, pos)
                v_bufs[li] = jax.vmap(row_write)(v_bufs[li], vq, pos)
                ks_bufs[li] = jax.vmap(row_write)(ks_bufs[li], ksc, pos)
                vs_bufs[li] = jax.vmap(row_write)(vs_bufs[li], vsc, pos)
            else:
                k_bufs[li] = jax.vmap(row_write)(
                    k_bufs[li], k_new.astype(c.dtype), pos)
                v_bufs[li] = jax.vmap(row_write)(
                    v_bufs[li], v_new.astype(c.dtype), pos)

            def _xla_attend(q, kb, vb, ksb, vsb):
                if self.quantize:
                    kb = _dequantize(kb, ksb, c.dtype)
                    vb = _dequantize(vb, vsb, c.dtype)
                return _attend(q, kb, vb, mask, scale)

            if self._flash:
                def _fused_attend(q, kb, vb, ksb, vsb):
                    return _attend(
                        q, kb, vb, mask, scale, pos=pos0, flash=True,
                        k_scale=ksb if self.quantize else None,
                        v_scale=vsb if self.quantize else None,
                    )

                out = jax.lax.cond(
                    lockstep, _fused_attend, _xla_attend,
                    q, k_bufs[li], v_bufs[li], ks_bufs[li], vs_bufs[li],
                )
            else:
                out = _xla_attend(q, k_bufs[li], v_bufs[li],
                                  ks_bufs[li], vs_bufs[li])
            h = h + out @ layer["wo"]
            h = h + _ffn(_rms_norm(h, layer["ffn_norm"], c.norm_eps),
                         layer, c)
        x = _rms_norm(h, params["final_norm"], c.norm_eps)
        logits = (x[:, 0] @ params["lm_head"]).astype(jnp.float32)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pos = pos + active.astype(jnp.int32)
        return (nxt, tuple(k_bufs), tuple(v_bufs), tuple(ks_bufs),
                tuple(vs_bufs), pos)

    def step(self, tokens: Sequence[int],
             active: Sequence[bool]) -> List[int]:
        import jax.numpy as jnp

        self._note_shape(("step",))
        (nxt, self._k, self._v, self._ks, self._vs,
         self._pos) = self._step_jit(
            self._params, self._k, self._v, self._ks, self._vs,
            self._pos,
            jnp.asarray(list(tokens), jnp.int32),
            jnp.asarray(list(active), bool),
        )
        return [int(t) for t in nxt]

    def set_params(self, params) -> None:
        """Swap the weights in place (peer warm-start). Params are a jit
        ARGUMENT, not a captured constant, so no retrace happens — only
        the slot caches would be stale, and a warm-started replica has no
        occupants yet."""
        self._params = params
        self.params = params


def export_params(params) -> bytes:
    """Serialize a params pytree to one self-describing blob (msgpack of
    ``{keystr path: {dtype, shape, data}}``) — the payload a serving
    replica's fabric ``weights`` provider serves to warm-starting peers."""
    import jax
    import msgpack
    import numpy as np

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        arr = np.asarray(leaf)
        out[jax.tree_util.keystr(path)] = {
            "dtype": arr.dtype.str,
            "shape": list(arr.shape),
            "data": arr.tobytes(),
        }
    return msgpack.packb(out, use_bin_type=True)


def import_params(blob: bytes):
    """Inverse of :func:`export_params`: rebuild the nested-dict params
    pytree (all interior nodes are string-keyed dicts, which is what
    ``models/llama.py`` params look like)."""
    import re

    import jax.numpy as jnp
    import msgpack
    import numpy as np

    tree: dict = {}
    for path, spec in msgpack.unpackb(blob, raw=False).items():
        keys = re.findall(r"\['([^']*)'\]", path)
        if not keys:
            raise ValueError(f"unsupported params path {path!r}")
        node = tree
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = jnp.asarray(
            np.frombuffer(spec["data"], np.dtype(spec["dtype"]))
            .reshape(spec["shape"])
        )
    return tree


def build_tiny_engine(slots: int = 4, cache_len: int = 48,
                      vocab: int = 32, dim: int = 16, n_layers: int = 2,
                      n_heads: int = 2, n_kv_heads: int = 1,
                      seed: int = 0, quantize: bool = False,
                      dtype=None) -> BatchDecodeEngine:
    """CPU-sized jax engine with DETERMINISTIC params: every replica
    built from the same seed holds identical weights, so re-routing a
    request mid-stream reproduces the exact same tokens (the e2e zero-
    loss assertion depends on this). ``quantize``/``dtype`` pick the
    cache layout (int8 vs ``dtype``, default f32) — same weights either
    way, so the bench's int8-vs-bf16 pair differs ONLY in the cache."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig, init_params

    config = LlamaConfig(
        vocab_size=vocab, dim=dim, n_layers=n_layers, n_heads=n_heads,
        n_kv_heads=n_kv_heads, ffn_dim=4 * dim, max_seq_len=cache_len,
        dtype=dtype if dtype is not None else jnp.float32, remat=False,
    )
    params = init_params(config, jax.random.PRNGKey(seed))
    return BatchDecodeEngine(params, config, slots=slots,
                             cache_len=cache_len, quantize=quantize)
