"""Device milliseconds a whole step that latent attention's own path
takes outside the flash kernels, on chip 0: the self times of the ops
whose HLO line holds an array as wide as the latent (``kv_lora_rank``,
512) or as the down-projection's output (``kv_lora_rank`` +
``qk_rope_head_dim``, 576), forward, remade under remat and backward:
the down-projection and its gradient, the latent norm, the
up-projection from the latent and its gradient, the rope key's split
and the optimizer's passes over those leaves (``models/mla.py``'s
``mla_latent`` scope; a profile's op name is its HLO line, which holds
no scope). Not the query projection, the key and value concatenation
over the heads, the kernels or the output projection, which hold no such
width. None without a trace, a latent attention configuration or such an
op."""

from benchmarks.harness import op_rules


def read(ctx):
    fields = ctx.get("fields") or {}
    if "kv_lora_rank" not in fields:
        return None
    widths = {fields["kv_lora_rank"],
              fields["kv_lora_rank"] + fields["qk_rope_head_dim"]}
    return op_rules.step_ms(
        ctx, lambda name: op_rules.plain_op(name)
        and bool(widths & op_rules.dims_in_line(name)))
