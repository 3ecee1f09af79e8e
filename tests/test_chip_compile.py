"""The main path's kernels and train step compiled for a described v5e.

No chip is attached here: the TPU compiler that is installed compiles for
a ``v5e:2x2`` topology that is only described (on-chip-measurement guide,
section 2, rehearsal 3). It refuses what the chip's compiler would refuse
— unaligned slices, too much VMEM, a program that does not fit — which
interpret mode never shows. Shapes are ``chip_smoke.py``'s: Llama-2-7B
widths, 32 heads x 128, sequence 2048.

The topology is described inside a module-scoped fixture, in the test's
own process, never at import: only one process at a time may load libtpu,
and every xdist worker imports this file.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import (
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from dlrover_tpu.models import llama, moe
from dlrover_tpu.ops.flash_attention import (
    flash_attention,
    flash_decode_attention,
)
from dlrover_tpu.parallel.mesh import build_mesh, plan_mesh
from dlrover_tpu.trainer.elastic import ElasticTrainer, make_train_state

B, H, S, D = 1, 32, 2048, 128        # attention, as chip_smoke.py
KERNEL_NAMES = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
BD, T, POS = 8, 2048, 1500           # decode batch, cache length, position
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a described-chip compile is written to the persistent cache but
    # cannot be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_forward(one_chip):
    qkv = [_shape((B, H, S, D), jnp.bfloat16, one_chip)] * 3
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False), *qkv)
    assert text.count("tpu_custom_call") == 1


def test_flash_attention_forward_backward(one_chip):
    qkv = [_shape((B, H, S, D), jnp.bfloat16, one_chip)] * 3
    text = _compiled_text(
        jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)),
        *qkv)
    # forward, dq, dk/dv
    assert text.count("tpu_custom_call") == 3


def _source(text, name):
    """The instruction that ``name`` copies, following copies (into and
    out of the fast memory) and bitcasts back to their origin."""
    import re

    while True:
        found = re.search(
            rf"^\s*{re.escape(name)} = .*? (copy|copy-start|copy-done|"
            rf"bitcast)\((?:\([^)]*\) )?(%[\w.-]+)", text, re.MULTILINE)
        if not found:
            return name
        name = found.group(2)


@pytest.mark.parametrize("heads", [16, 32])
def test_flash_block_classes_lower_at_the_cells_shapes(one_chip, heads):
    """The cells' attention (sequence 4,096, head dim 128, the default
    blocks; 16 heads in the looped cell, 32 in the dense ones), forward and
    backward: the branched bodies and the clamped index maps of all three
    classes of step lower through Mosaic, and the program makes one call
    of each kernel with the queries as its first operand, which is what
    the benchmark's ``kernel_share`` reads."""
    import re

    from dlrover_tpu.observability.registry import get_registry
    from dlrover_tpu.ops.flash_attention import BlockPlan, flash_block_plan

    seq = 4096
    plan = flash_block_plan(seq, seq, 512, 1024, True)
    assert plan == BlockPlan(skip=12, full=12, masked=8)
    steps = get_registry().counter(
        "dlrover_flash_grid_steps_total", labelnames=("kernel", "block"))

    def read():
        return {(kernel, block): steps.labels(kernel=kernel,
                                              block=block).value
                for kernel in KERNEL_NAMES for block in BlockPlan._fields}

    before = read()
    qkv = [_shape((1, heads, seq, D), jnp.bfloat16, one_chip)] * 3
    text = _compiled_text(
        jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)),
        *qkv)
    after = read()
    for (kernel, block), n in after.items():
        assert n - before[(kernel, block)] == heads * getattr(plan, block)
    (query,) = re.findall(
        r'(%\S+) = \S+ parameter\(0\).*op_name="q"', text)
    for kernel in KERNEL_NAMES:
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line
                 and re.match(rf"\s*%\w*{kernel}_", line)]
        assert len(calls) == 1, (kernel, calls)
        first = calls[0].partition("custom-call(")[2].split(",")[0]
        assert _source(text, first) == query, (kernel, first)


def test_flash_kernels_lower_with_values_of_another_width(one_chip):
    """Latent attention's kernels (``benchmarks/configs/
    moonlight-16b-a3b``: 16 heads, 8,192 positions, queries and keys 192
    wide over values 128 wide, the default blocks), forward and
    backward: Mosaic takes the unequal widths, one call of each kernel,
    the output and the value gradient 128 wide, and the forward's first
    operand is the 192-wide queries that ``kernel_share`` reads."""
    seq, heads = 8192, 16
    qk = _shape((1, heads, seq, 192), jnp.bfloat16, one_chip)
    v = _shape((1, heads, seq, 128), jnp.bfloat16, one_chip)
    text = _compiled_text(
        jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)),
        qk, qk, v)
    import re

    calls = {kernel: [line for line in text.splitlines()
                      if 'custom_call_target="tpu_custom_call"' in line
                      and re.match(rf"\s*%\w*{kernel}_", line)]
             for kernel in KERNEL_NAMES}
    assert [len(c) for c in calls.values()] == [1, 1, 1], calls
    (forward,) = calls["flash_fwd"]
    assert forward.split()[2].startswith(f"(bf16[1,{heads},{seq},128]")
    # the first four-dimensional array after the call's opening, as
    # ``named_kernels.query_shape`` reads a profile's line
    operands = forward.partition("custom-call(")[2]
    first = re.search(r"\b[a-z]+\d+\[\d+,\d+,\d+,\d+\]", operands)
    assert first.group(0) == f"bf16[1,{heads},{seq},192]"


@pytest.mark.parametrize("kv_heads,quantized", [
    pytest.param(32, False, id="bf16"),
    pytest.param(32, True, id="int8-MHA"),
    pytest.param(8, True, id="int8-GQA"),
])
def test_flash_decode_attention(one_chip, kv_heads, quantized):
    cache_dtype = jnp.int8 if quantized else jnp.bfloat16
    q = _shape((BD, kv_heads, H // kv_heads, D), jnp.bfloat16, one_chip)
    kv = _shape((BD, kv_heads, T, D), cache_dtype, one_chip)
    scales = [_shape((BD, kv_heads, T), jnp.float32, one_chip)] * 2

    def fn(q, k, v, *s):
        return flash_decode_attention(
            q, k, v, POS, interpret=False,
            k_scale=s[0] if s else None, v_scale=s[1] if s else None)

    text = _compiled_text(fn, q, kv, kv, *(scales if quantized else ()))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,dtype", [
    ((32000, 4096), "float32"),        # an embedding's moment: 500 MiB
    ((1, 4096, 14336), "bfloat16"),    # a stacked FFN weight: 112 MiB
])
def test_restore_rebuilds_a_large_leaf_in_its_own_bytes(topo, shape, dtype):
    """``ckpt/engine.py`` ``_rebuild_program`` at the benchmark's leaves:
    the blocks a restore puts chunk by chunk become the leaf, and the
    program holds nothing beyond them and it but, for a narrow float
    that travels as integers, one copy of the leaf — none padded by the
    tiling of a two- or four-byte minor dimension."""
    import numpy as np

    from dlrover_tpu.ckpt import engine

    itemsize = engine._np_dtype(dtype).itemsize
    block_shape, blocks = engine._row_blocks(shape, itemsize)
    compiled = engine._rebuild_program.__wrapped__(
        shape, dtype, block_shape, tuple(start for _, start, _ in blocks),
        topo.devices[0])
    leaf = int(np.prod(shape)) * itemsize
    block = int(np.prod(block_shape)) * itemsize
    assert block <= engine._PACK_CHUNK_BYTES
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == leaf
    assert memory.argument_size_in_bytes == len(blocks) * block
    copies = engine._carrier(dtype) != engine._np_dtype(dtype)
    assert memory.temp_size_in_bytes <= block + copies * leaf


def test_expert_layer_over_ep4_runs_the_grouped_kernel(topo, monkeypatch):
    """``moe._moe_ffn`` at Mixtral-8x7B widths over ``ep`` 4, forward and
    backward: every projection and each of its two gradients is one
    grouped-matmul kernel over the ``T·k`` sorted rows at a quarter of
    every expert's columns, inside a ``shard_map`` (a Mosaic call cannot
    be partitioned by GSPMD); no array of the program is laid out by
    expert slots, and the chips exchange nothing but all-reduces."""
    # the layer picks its grouped matmul from the default backend, which
    # is the CPU here: steer it, as the chip would
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = moe.MoEConfig(
        n_layers=1, capacity_factor=4.0, route_group_size=512,
        max_seq_len=4096)
    mesh = build_mesh(plan_mesh(4, ep=4), devices=list(topo.devices))
    whole = NamedSharding(mesh, P())
    by_column = NamedSharding(mesh, P(None, None, "ep"))
    E, D, F, T = cfg.n_experts, cfg.dim, cfg.ffn_dim, 4096
    layer = {
        "router": _shape((D, E), jnp.float32, whole),
        "w1": _shape((E, D, F), jnp.bfloat16, by_column),
        "w3": _shape((E, D, F), jnp.bfloat16, by_column),
        "w2": _shape((E, F, D), jnp.bfloat16,
                     NamedSharding(mesh, P(None, "ep", None))),
    }
    x = _shape((1, T, D), jnp.bfloat16, whole)

    def loss(x, layer):
        out, aux = moe._moe_ffn(x, layer, cfg, mesh)
        return out.astype(jnp.float32).sum() + aux

    text = _compiled_text(jax.grad(loss, argnums=(0, 1)), x, layer)
    calls = [line.split()[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # three projections x (forward, gradient of the rows, of the weights)
    assert len(calls) == 9, calls
    assert sum("tgmm" in name for name in calls) == 3, calls
    rows, columns = T * cfg.top_k, F // 4
    assert f"bf16[{rows},{columns}]" in text
    assert f"bf16[{E},{D},{columns}]" in text     # every expert, sliced
    assert f"bf16[{rows},{F}]" not in text
    slots = moe.expert_capacity(cfg, 1, T)
    assert f"{F},{T // 512},{slots}]" not in text     # the old (e,f,g,c)
    assert f"{slots},{F}]" not in text
    for other in ("all-gather", "all-to-all", "collective-permute",
                  "reduce-scatter"):
        assert f" {other}(" not in text and f" {other}-start(" not in text
    assert " all-reduce" in text


def test_train_step_one_layer_fits_the_chip(topo, monkeypatch):
    """``ElasticTrainer._build_step`` at 7B widths, depth 1: the flash
    kernel is in the program forward and backward, each of the three
    kernels once, and XLA's own memory analysis stays under the chip's
    16 GB."""
    _on_chip(monkeypatch)
    cfg = dataclasses.replace(
        llama.LlamaConfig.llama7b(), n_layers=1, max_seq_len=S,
        use_flash_attention=True,
    )
    plan = plan_mesh(1)
    mesh = build_mesh(plan, devices=list(topo.devices))
    on_mesh = NamedSharding(mesh, P())
    optimizer = optax.adamw(3e-4)
    trainer = ElasticTrainer(
        loss_fn=lambda p, t: llama.next_token_loss(p, t, cfg, mesh),
        optimizer=optimizer, global_batch_size=4, micro_batch_per_replica=2,
    )
    trainer.configure_for_world(plan)
    state = jax.eval_shape(
        lambda: make_train_state(
            llama.init_params(cfg, jax.random.PRNGKey(0)), optimizer))
    state = jax.tree.map(
        lambda x: _shape(x.shape, x.dtype, on_mesh), state)
    tokens = _shape((2, 2, S + 1), jnp.int32, on_mesh)
    lowered = trainer._build_step().lower(state, tokens)
    text = lowered.as_text()
    for kernel in KERNEL_NAMES:
        assert f'"{kernel}"' in text
    compiled = lowered.compile()
    # the compiled program's custom calls carry the kernels' names as
    # their own (``%flash_fwd.13 = ... custom-call(``), and a profile
    # names an op's events by that line
    calls = _kernel_calls(compiled.as_text())
    for kernel in KERNEL_NAMES:
        assert _count(calls, kernel) == 1, calls
    mem = compiled.memory_analysis()
    # the donated state is aliased to the output: counted once
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.alias_size_in_bytes > 0.99 * mem.argument_size_in_bytes, (
        "the step must alias its whole donated state")
    assert peak < HBM_BYTES, f"{peak / 2**30:.1f} GiB"


COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter")


def _collectives(text):
    """``op result-shape`` of every collective of a compiled program."""
    found = []
    for line in text.splitlines():
        for op in COLLECTIVES:
            if f" {op}(" in line or f" {op}-start(" in line:
                found.append(op + " " + line.split(" = ")[1].split(
                    f" {op}")[0])
    return found


def _kernel_calls(text):
    """Names of the Pallas calls of a compiled program
    (``%flash_fwd.13 = ... custom-call(``)."""
    return [line.split()[0] for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _count(calls, kernel):
    return sum(name.startswith(f"%{kernel}.") for name in calls)


def _on_chip(monkeypatch):
    """Steer what the step picks from the default backend, which is the
    CPU here, as the chip would: no interpret mode, the Pallas kernels."""
    fa = importlib.import_module("dlrover_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_default_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


EXPERT_SEQ = 4096


@pytest.fixture(scope="module")
def expert_step(topo):
    """The expert cell's whole step (``benchmarks/configs/mixtral-8x7b``:
    published widths, depth 1, ``ep`` 4, two microbatches of one row of
    4,096 tokens), compiled once for the tests below: (compiled text,
    the configuration's fields, the parameters' shardings)."""
    import json
    import os

    from benchmarks.families import mixtral_moe as family
    from dlrover_tpu.parallel.sharding import valid_spec_for

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks", "configs", "mixtral-8x7b.json")) as f:
        fields = json.load(f)
    plan = plan_mesh(4, **fields["mesh"])
    mesh = build_mesh(plan, devices=list(topo.devices))
    cfg = family.program_config(fields, EXPERT_SEQ)
    optimizer = optax.adamw(3e-4)
    shapes = jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.PRNGKey(0)))
    is_axes = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        isinstance(n, (str, type(None))) for n in x)
    on_mesh = jax.tree.map(
        lambda axes, leaf: NamedSharding(
            mesh, valid_spec_for(mesh, leaf.shape, axes)),
        family.logical_axes(cfg), shapes, is_leaf=is_axes)
    whole = NamedSharding(mesh, P())
    state = jax.eval_shape(lambda: make_train_state(shapes, optimizer))
    # the moments lie as their parameters do, the counters on every chip
    state = jax.tree.map(
        lambda x: _shape(x.shape, x.dtype, whole), state)
    like = lambda tree: jax.tree.map(  # noqa: E731
        lambda x, s: _shape(x.shape, x.dtype, s), tree, on_mesh)
    adam = state["opt_state"][0]
    state["opt_state"] = (
        adam._replace(mu=like(adam.mu), nu=like(adam.nu)),
        *state["opt_state"][1:])
    state["params"] = like(state["params"])
    trainer = ElasticTrainer(
        loss_fn=family.loss_fn(cfg, mesh), optimizer=optimizer,
        global_batch_size=2, micro_batch_per_replica=1)
    trainer.configure_for_world(plan)
    tokens = _shape((2, 1, EXPERT_SEQ + 1), jnp.int32, whole)
    with pytest.MonkeyPatch.context() as monkeypatch:
        _on_chip(monkeypatch)
        text = trainer._build_step().lower(state, tokens).compile().as_text()
    return text, fields, on_mesh


def test_expert_cell_step_never_holds_the_logits_whole(expert_step):
    """The expert cell's step with the vocabulary of head and embedding
    spread over the group: no array of the program is logits-shaped at
    the whole vocabulary, none has a dimension of ``vocab_size`` at all,
    nothing vocabulary-sized is gathered, and what the chips exchange is
    all-reduces only: of hidden states (the experts' sum, the heads'
    sum after ``wo`` and before the projections' input gradient, the
    embedding's rows, the head's gradient), of per-token f32 statistics
    and of scalars. None sums q, k or v: attention's heads are split
    over the group as the projections made them."""
    import math
    import re

    text, fields, on_mesh = expert_step
    seq, vocab = EXPERT_SEQ, fields["vocab_size"]
    hidden = fields["hidden_size"]
    assert on_mesh["lm_head"].shard_shape((hidden, vocab)) \
        == (hidden, vocab // 4)
    found = _collectives(text)
    said = "collectives of the step:\n  " + "\n  ".join(found)
    logits = re.findall(rf"\w+\[(?:\d+,)*{seq},{vocab}\]", text)
    assert not logits, f"logits-shaped arrays {sorted(set(logits))}\n{said}"
    wide = re.findall(rf"\w+\[(?:\d+,)*{vocab}(?:,\d+)*\]", text)
    assert not wide, f"vocabulary-sized arrays {sorted(set(wide))}\n{said}"
    assert f"f32[1,{seq},{vocab // 4}]" in text, said   # a chip's logits
    assert all(c.startswith("all-reduce ") for c in found), said
    # hidden states: the experts' sum and the heads' sum, forward and
    # backward, the embedding's rows, the head's gradient
    hidden_sums = [c for c in found
                   if re.match(rf"all-reduce bf16\[(1,)?{seq},{hidden}\]", c)]
    assert len(hidden_sums) == 6, said
    # the head's statistics: maximum, summed exponentials, target's logit
    assert sum(c.count(f"f32[1,{seq}]") for c in found) >= 3, said
    # and nothing else as large as a token's hidden state: no attention
    # gradient, bf16[1,32,seq,128] where the heads were replicated
    for c in found:
        if c in hidden_sums:
            continue
        sizes = [math.prod(int(d) for d in dims.split(",") if d)
                 for dims in re.findall(r"\[([\d,]*)\]", c)]
        assert max(sizes, default=1) <= 2 * seq, f"{c}\n{said}"


def test_expert_cell_step_runs_the_forward_kernel_once(expert_step):
    """The expert cell's layer under ``jax.checkpoint`` keeps the flash
    kernel's output and log-sum-exp, so its backward pass runs no second
    forward kernel: one call of each of the three a step program (the
    microbatch loop's body holds one layer)."""
    calls = _kernel_calls(expert_step[0])
    for kernel in KERNEL_NAMES:
        assert _count(calls, kernel) == 1, calls


def test_looped_step_runs_the_forward_kernel_once(topo, monkeypatch):
    """The looped model's step at chip-aligned widths (2 layers run 2
    passes, heads 128 wide, full remat as ``benchmarks/configs/ouro-2.6b``
    has it): the backward pass reads the output and log-sum-exp the
    forward kernel made, so the program calls each kernel once, where
    replaying the whole layer called the forward kernel twice."""
    from dlrover_tpu.models import looped

    _on_chip(monkeypatch)
    seq = 1024
    cfg = looped.LoopedConfig(
        vocab_size=2048, dim=256, n_layers=2, n_heads=2, n_kv_heads=2,
        ffn_dim=512, max_seq_len=seq, n_passes=2, use_flash_attention=True)
    assert cfg.remat and cfg.remat_policy is None
    plan = plan_mesh(1)
    mesh = build_mesh(plan, devices=list(topo.devices))
    on_mesh = NamedSharding(mesh, P())
    optimizer = optax.adamw(3e-4)
    trainer = ElasticTrainer(
        loss_fn=looped.make_loss_fn(cfg, mesh), optimizer=optimizer,
        global_batch_size=2, micro_batch_per_replica=1)
    trainer.configure_for_world(plan)
    state = jax.eval_shape(
        lambda: make_train_state(
            looped.init_params(cfg, jax.random.PRNGKey(0)), optimizer))
    state = jax.tree.map(lambda x: _shape(x.shape, x.dtype, on_mesh), state)
    tokens = _shape((2, 1, seq + 1), jnp.int32, on_mesh)
    text = trainer._build_step().lower(state, tokens).compile().as_text()
    calls = _kernel_calls(text)
    for kernel in KERNEL_NAMES:
        assert _count(calls, kernel) == 1, calls
