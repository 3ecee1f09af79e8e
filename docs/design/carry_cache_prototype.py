"""Round-3 lead, RESOLVED in round 4 (kept as the measurement record).

The hypothesis here — carry the FULL (L,B,KV,T,Dh) cache through the
layer scan, update one row per layer at a traced layer index — was
MEASURED AND REJECTED on v5e: XLA does not in-place a
dynamic_update_slice at a traced leading index inside a scan carry; it
copies the whole stacked buffer at every layer (36.6 ms/step at 2k ctx,
vs 13 ms for the r3 xs/ys slicing design it meant to fix). What XLA's
in-place-DUS optimization DOES match is one buffer per layer written by
an UNROLLED layer loop — 4.5 ms/step, 78% of the HBM roof — which is
what models/decode.py ships since round 4 (per-layer cache tuples).
``step_carry`` below is the rejected variant, runnable for comparison:
python docs/design/carry_cache_prototype.py  (NOTE: decode.decode_step
no longer accepts the stacked cache this harness builds; the harness is
self-contained and only meaningful as the A/B it records.)
"""
import sys, time, functools
sys.path.insert(0, "/root/repo")
import jax, jax.numpy as jnp
from dlrover_tpu.models import decode, llama
from dlrover_tpu.models.llama import _rms_norm, _rope, _mlp
from dlrover_tpu.models.decode import _split_heads, _attend

dim, layers = 2048, 16
heads = dim // 128
B, T = 8, 2176
c = llama.LlamaConfig(vocab_size=32000, dim=dim, n_layers=layers, n_heads=heads,
    n_kv_heads=heads//2, ffn_dim=int(2.75*dim)//256*256, max_seq_len=T, remat=False)
params = llama.init_params(c, jax.random.PRNGKey(0))
prompt = jax.random.randint(jax.random.PRNGKey(1), (B, 2048), 0, 32000)
logits, cache = jax.jit(functools.partial(decode.prefill, config=c, max_len=T))(params, prompt)
# prefill returns per-layer tuples (the shipped layout); the rejected
# carry variant needs the layer-stacked buffer it was specified against
stacked = {"k": jnp.stack(cache["k"]), "v": jnp.stack(cache["v"]),
           "pos": cache["pos"]}
tok = jnp.ones((B,), jnp.int32)
probe = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))
_ = float(probe(jnp.ones((8,)))); t0=time.perf_counter()
for _ in range(3): _ = float(probe(jnp.ones((8,))))
rtt = (time.perf_counter()-t0)/3

def step_carry(p, token, cch):
    """Cache stays in the scan CARRY; per-layer row update is an in-place
    dynamic_update_slice on the full (L,B,KV,T,Dh) buffer."""
    pos = cch["pos"]
    x = p["tok_embed"][token][:, None, :]
    positions = jnp.broadcast_to(pos[None, None], (B, 1))
    mask = (jnp.arange(T)[None, None, None, :] <= pos)
    scale = c.head_dim ** -0.5
    def layer_fn(carry, inputs):
        h, kc, vc = carry
        layer, li = inputs
        xn = _rms_norm(h, layer["attn_norm"], c.norm_eps)
        q = _rope(_split_heads(xn @ layer["wq"], c.n_heads, c.head_dim), positions, c.rope_theta)
        k_new = _rope(_split_heads(xn @ layer["wk"], c.n_kv_heads, c.head_dim), positions, c.rope_theta)
        v_new = _split_heads(xn @ layer["wv"], c.n_kv_heads, c.head_dim)
        k_new = jnp.swapaxes(k_new, 1, 2).astype(kc.dtype)[None]
        v_new = jnp.swapaxes(v_new, 1, 2).astype(vc.dtype)[None]
        kc = jax.lax.dynamic_update_slice(kc, k_new, (li, 0, 0, pos, 0))
        vc = jax.lax.dynamic_update_slice(vc, v_new, (li, 0, 0, pos, 0))
        k_l = jax.lax.dynamic_index_in_dim(kc, li, 0, keepdims=False)
        v_l = jax.lax.dynamic_index_in_dim(vc, li, 0, keepdims=False)
        out = _attend(q, k_l, v_l, mask, scale, pos=None)
        h = h + out @ layer["wo"]
        h = h + _mlp(_rms_norm(h, layer["ffn_norm"], c.norm_eps), layer)
        return (h, kc, vc), ()
    (x, kc, vc), _ = jax.lax.scan(
        layer_fn, (x, cch["k"], cch["v"]),
        (p["layers"], jnp.arange(c.n_layers)))
    x = _rms_norm(x, p["final_norm"], c.norm_eps)
    logits = (x[:, 0] @ p["lm_head"]).astype(jnp.float32)
    return logits, {"k": kc, "v": vc, "pos": pos + 1}

iters = 64
def bench(label, step_fn, cch0):
    # params is an ARGUMENT, not a closure: closing over 2 GB of device
    # arrays makes jit lowering embed them as constants and fetch them
    # host-side before compiling starts
    @functools.partial(jax.jit, donate_argnums=(2,))
    def loop(p, t, cch):
        def body(carry, _):
            lg, cc = step_fn(p, t, carry)
            return cc, lg[0, 0]
        cc, lgs = jax.lax.scan(body, cch, None, length=iters)
        return cc, lgs[-1]
    cc = jax.tree.map(jnp.copy, cch0)
    cc, lg = loop(params, tok, cc); _ = float(lg)
    cc = jax.tree.map(jnp.copy, cch0)
    t0 = time.perf_counter()
    cc, lg = loop(params, tok, cc); _ = float(lg)
    dt = (time.perf_counter()-t0-rtt)/iters
    print(f"{label}: {dt*1e3:.2f} ms/step ({1/dt:.1f} steps/s)", flush=True)

bench("shipped decode_step (unrolled per-layer)",
      lambda p, t, cc: decode.decode_step(p, t, cc, c), cache)
bench("rejected carry-cache scan               ",
      step_carry, stacked)
# correctness: logits must match
l1, _ = jax.jit(lambda p, t, cc: decode.decode_step(p, t, cc, c))(params, tok, cache)
l2, _ = jax.jit(step_carry)(params, tok, stacked)
import numpy as np
err = float(jnp.max(jnp.abs(l1 - l2)))
print("max logit err carry vs shipped:", err)
