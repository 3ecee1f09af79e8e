"""CPU checks of what is the expert cell's alone (PR 32): the family's
init spreads the pairs evenly over the chips of the ``ep`` group, and
the three readers that say what the step waits for. Run from the repo
root:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_expert_cell.py -q
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402

BENCH = bench_run.load_benchmark()
EXPERT_CELL = "mixtral-8x7b.train-steady"
EXPERT_READERS = ("moe.expert_ms", "moe.hot_chip_ratio", "collective.time_ms")
CHIPS = 4

# -- the family's init: the router sees tokens -------------------------------

# wide enough and long enough to show the fault: the program's default
# rows have rms 1/32 here where the causal mean over 2,048 tokens has
# about 0.05; 4,096 pairs, 1,024 a chip, sample the shares to 3 %
SMALL = {"hidden_size": 1024, "num_attention_heads": 8,
         "num_key_value_heads": 2, "head_dim": 128, "intermediate_size": 32,
         "vocab_size": 1024, "num_hidden_layers": 1}
SEQ = 2048
EVEN, UNEVEN = 1.2, 1.3   # hottest chip over mean: at most / at least


def pairs_a_chip(fields, params, tokens):
    """Step 1's pairs on each chip of an ``ep`` group of ``CHIPS`` (whole
    experts a chip, in order), by the plain float32 router of
    ``reference/decoder.py``: nothing of the program's routing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import decoder

    f = fields
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        layer = jax.tree.map(lambda a: a[0], p["layers"])
        x = p["tok_embed"][tokens]
        x = x + decoder._attention(decoder._rms_norm(
            x, layer["attn_norm"], f["rms_norm_eps"]), layer, f)
        h = decoder._rms_norm(x, layer["ffn_norm"], f["rms_norm_eps"])
        probs = jax.nn.softmax(
            h.reshape(-1, h.shape[-1]) @ layer["router"], axis=-1)
        _, chosen = jax.lax.top_k(probs, f["num_experts_per_tok"])
    experts = np.bincount(np.asarray(chosen).ravel(),
                          minlength=f["num_local_experts"])
    return experts.reshape(CHIPS, -1).sum(axis=1)


SEEDS = [1, 2, 3, 4, 2**31 + 5, 2**31 + 6]


def seeded(seed):
    """(fields, config, key, step 1's tokens) as ``jobs/train.py`` makes
    them from ``--seed``, at the small widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.families import mixtral_moe
    from benchmarks.tests.test_benchmark import config_fields

    fields = {**config_fields("mixtral-8x7b"), **SMALL}
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    tokens = jnp.asarray(np.random.default_rng([seed, 1]).integers(
        0, fields["vocab_size"], size=(1, SEQ), dtype=np.int32))
    return fields, mixtral_moe.program_config(fields, SEQ), key, tokens


def hottest_over_mean(pairs):
    return pairs.max() / pairs.mean()


@pytest.mark.parametrize("seed", SEEDS)
def test_the_familys_init_spreads_the_pairs_evenly(seed):
    """With the family's unit-rms rows no chip draws more than ``EVEN``
    times the mean share of step 1's pairs; nothing of the tree but the
    rows differs from the program's own init on the same key."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.families import mixtral_moe
    from dlrover_tpu.models import moe

    fields, config, key, tokens = seeded(seed)
    ours = mixtral_moe.init_params(config, key)
    theirs = moe.init_params(config, key)
    rows = np.asarray(ours["tok_embed"].astype(jnp.float32))
    assert np.sqrt(np.mean(rows * rows)) == pytest.approx(1.0, rel=0.02)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(ours),
                                 jax.tree_util.tree_leaves_with_path(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        if "tok_embed" not in jax.tree_util.keystr(path):
            assert bool(jnp.all(a == b)), path
    pairs = pairs_a_chip(fields, ours, tokens)
    assert pairs.sum() == SEQ * fields["num_experts_per_tok"]
    assert hottest_over_mean(pairs) <= EVEN, pairs


def test_the_sample_can_tell_the_two_inits_apart():
    """The program's default rows, on the same keys and tokens: the
    hottest chip draws more than under the family's init on every seed,
    and ``UNEVEN`` times the mean or more on most (the median)."""
    import statistics

    from benchmarks.families import mixtral_moe
    from dlrover_tpu.models import moe

    default = []
    for seed in SEEDS:
        fields, config, key, tokens = seeded(seed)
        default.append(hottest_over_mean(pairs_a_chip(
            fields, moe.init_params(config, key), tokens)))
        assert default[-1] > hottest_over_mean(pairs_a_chip(
            fields, mixtral_moe.init_params(config, key), tokens)), seed
    assert statistics.median(default) >= UNEVEN, default


# -- the readers, on a hand-made profile of four chips -----------------------

_T = "{1,0:T(8,128)(2,1)}"
GMM = (f"%gmm.7 = bf16[8192,14336]{_T} custom-call(bf16[8192,4096]{_T} %p), "
       'custom_call_target="tpu_custom_call"')
SILU = f"%fusion.3 = bf16[8192,14336]{_T} fusion(bf16[8192,14336]{_T} %gmm.7)"
ADAMW = (f"%fusion.8 = bf16[1,2,4096,14336]{_T} fusion("
         f"bf16[1,2,4096,14336]{_T} %param.3)")
ALL_REDUCE = f"%all-reduce.2 = bf16[1,4096,4096]{_T} all-reduce(%fusion.9)"
STEP_NS = 10_000_000
# a chip's grouped matmul, and what is left of the step's 3 ms of waiting
GMM_NS = (1_000_000, 3_000_000, 2_000_000, 2_000_000)
REDUCE_NS = 100_000            # the all-reduce's own cost


def four_planes(steps=3):
    """Each chip runs ``steps`` whole step programs and one more that the
    profile's edge cut after its first op. In a step: the grouped matmul
    (as long as the chip's share of the pairs), a SiLU pass of 0.2 ms,
    the all-reduce (its own 0.1 ms and the wait for the hottest chip),
    AdamW over the expert leaves (no expert op)."""
    planes = []
    for chip, gmm_ns in enumerate(GMM_NS):
        ops, modules = [], []
        for step in range(steps + 1):
            at = step * STEP_NS
            modules.append(["jit_step_fn(1)", at, STEP_NS - 1000])
            wait = max(GMM_NS) - gmm_ns
            for name, dur in ((GMM, gmm_ns), (SILU, 200_000),
                              (ALL_REDUCE, REDUCE_NS + wait),
                              (ADAMW, 500_000)):
                ops.append([name, at + 10, dur])
                at += dur + 10
                if step == steps:
                    break
        planes.append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]})
    return {"planes": planes}


def reader_ctx(trace):
    from benchmarks.harness import trace_reduce

    return {"trace_raw": trace, "step_module": "step_fn", "job": {},
            "trace": trace and trace_reduce.reduce(
                trace, step_module="step_fn"),
            "fields": {"hidden_size": 4096, "intermediate_size": 14336,
                       "num_local_experts": 8}}


def test_the_expert_readers_say_what_the_step_waits_for(capsys):
    import json

    ctx = reader_ctx(four_planes())
    read = {n: bench_run.load_reader(n).read for n in EXPERT_READERS}
    by_chip = [(ns + 200_000) / 1e6 for ns in GMM_NS]
    # the largest over the chips; the cut step is left out
    assert read["moe.expert_ms"](ctx) == pytest.approx(max(by_chip))
    assert read["moe.hot_chip_ratio"](ctx) == pytest.approx(
        max(by_chip) / (sum(by_chip) / CHIPS))
    # the smallest over the chips: the hot chip's, which waits for nobody.
    # trace_reduce counts the cut step program as a step and its ops with
    # the others', which is the older rule and 1 / steps off
    steps = 4
    assert read["collective.time_ms"](ctx) == pytest.approx(
        (steps - 1) * REDUCE_NS / 1e6 / steps)
    notes = {n["note"]: n for n in map(
        json.loads, capsys.readouterr().out.splitlines())}
    assert notes["expert_ms_by_chip"]["chips"] == pytest.approx(by_chip)
    assert notes["expert_ms_by_chip"]["whole_steps"] == 3
    assert notes["expert_ms_by_chip"]["last_step_ratio"] == pytest.approx(
        read["moe.hot_chip_ratio"](ctx))
    assert notes["collective_ms_by_chip"]["longest_wait_ms"] == pytest.approx(
        (steps - 1) * (max(GMM_NS) - min(GMM_NS)) / 1e6 / steps)


def test_an_even_split_reads_one_and_no_trace_reads_nothing():
    even = four_planes()
    for plane in even["planes"][1:]:
        plane["lines"] = [dict(line) for line in even["planes"][0]["lines"]]
    ratio = bench_run.load_reader("moe.hot_chip_ratio").read
    assert ratio(reader_ctx(even)) == pytest.approx(1.0)
    # one chip has no ratio; no trace, or a dense configuration, nothing
    one = {"planes": four_planes()["planes"][:1]}
    assert ratio(reader_ctx(one)) is None
    for name in EXPERT_READERS:
        read = bench_run.load_reader(name).read
        assert read(reader_ctx(None)) is None, name
    dense = reader_ctx(four_planes())
    del dense["fields"]["num_local_experts"]
    assert ratio(dense) is None
    assert bench_run.load_reader("moe.expert_ms").read(dense) is None


def sliced(trace):
    """The same profile as the program of PR 33 writes it: every chip
    holds all eight experts at a quarter of their columns, so a buffer of
    routed rows is ``bf16[8192,3584]`` and an expert leaf
    ``bf16[8,4096,3584]``."""
    import json

    return json.loads(json.dumps(trace).replace(
        "[1,2,4096,14336]", "[8,4096,3584]").replace("14336", "3584"))


@pytest.mark.parametrize("shards,layout,reads", [
    (4, "sliced", True),     # the expert cell since PR 33
    (None, "sliced", False),  # the rule of PR 30 to PR 34 on it: silent
    (4, "whole", False),     # the local width finds no whole-width array
    (1, "whole", True),      # one chip, or whole experts on chips
], ids=["local-width", "whole-width-rule-on-sliced-columns",
        "local-width-rule-on-whole-experts", "no-mesh"])
def test_the_expert_rule_takes_the_width_a_chip_holds(shards, layout, reads):
    """``harness/expert_ops.py`` looks for ``intermediate_size`` over
    ``job["expert_mlp_shards"]`` (``jobs/train.py``: the product of the
    mesh axes ``DEFAULT_RULES["expert_mlp"]`` names): both expert readers
    read ``bf16[8192,3584]`` rows in a four-plane trace of the sliced
    layout, and AdamW over ``bf16[8,4096,3584]`` stays no expert op."""
    trace = four_planes()
    ctx = reader_ctx(sliced(trace) if layout == "sliced" else trace)
    if shards is not None:
        ctx["job"] = {"expert_mlp_shards": shards}
    by_chip = [(ns + 200_000) / 1e6 for ns in GMM_NS]
    expert_ms = bench_run.load_reader("moe.expert_ms").read(ctx)
    ratio = bench_run.load_reader("moe.hot_chip_ratio").read(ctx)
    if reads:
        assert expert_ms == pytest.approx(max(by_chip))
        assert ratio == pytest.approx(max(by_chip) / (sum(by_chip) / CHIPS))
    else:
        assert expert_ms is None and ratio is None


def test_the_job_says_how_many_chips_share_an_experts_width():
    """The count ``jobs/train.py`` hands the readers: 4 on the expert
    cell's ``ep`` 4 mesh, 1 where the mesh has no axis over 1."""
    from dlrover_tpu.parallel.sharding import DEFAULT_RULES, axis_size

    class Mesh:
        def __init__(self, **shape):
            self.shape = shape

    axes = DEFAULT_RULES["expert_mlp"]
    assert axis_size(Mesh(ep=4, tp=1, fsdp=1), axes) == 4
    assert axis_size(Mesh(ep=2, tp=2), axes) == 4
    assert axis_size(Mesh(ep=1, tp=1, fsdp=4), axes) == 1


# -- which cell reports what --------------------------------------------------


def test_the_any_kernel_roofline_is_left_out_of_the_expert_cell():
    """``flash_attn_roofline`` counts every Pallas call, the grouped
    matmuls among them: the expert cell's line leaves it out and keeps
    the rooflines that find the flash kernels by name; the three one-chip
    cells' lines keep all three. Only the expert cell reads the experts'
    and the collectives' readers."""
    expected = {"mistral-7b.train-steady": True,
                "mistral-7b.train-flashsave": True,
                "ouro-2.6b.train-steady": True,
                EXPERT_CELL: False}
    for cell, any_kernel in expected.items():
        names = {m["name"] for m in bench_run.metrics_of(
            cell, BENCH["per_layer"])}
        assert {"flash_fwd_roofline", "flash_bwd_roofline"} <= names, cell
        assert ("flash_attn_roofline" in names) == any_kernel, cell
        for name in EXPERT_READERS:
            assert (name in names) == (cell == EXPERT_CELL), (cell, name)
    for name in EXPERT_READERS:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [EXPERT_CELL]
        assert entry["moves"] == "tokens_per_s"
        assert callable(bench_run.load_reader(name).read)
