"""The benchmark's families, fast and in tier-1: every family a
configuration names exports the contract's nine names, and the literals
the harness divides by hold through the families (not through
``harness/flops.py``, which only the plain decoders share).
``benchmarks/tests`` holds the slower checks (rehearsals of every cell)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402

BENCH = bench_run.load_benchmark()
CONFIGS = [c["name"] for c in BENCH["configs"]]
SEQ = 4096
DENSE_SCORE = 68_736_253_952   # one causal score-sized matmul, 32 heads
LOOP_SCORE = 34_368_126_976    # 16 heads of 128 at 4,096
MLA_SCORE = 268_500_992        # 16 heads at 4,096, a width of one


def fields_of(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("config", CONFIGS)
def test_every_family_a_configuration_names_exports_the_nine_names(config):
    assert len(bench_run.FAMILY_CONTRACT) == 9
    family = bench_run.load_family(fields_of(config)["family"])
    for name in bench_run.FAMILY_CONTRACT:
        assert hasattr(family, name), (config, name)
    for name in bench_run.FAMILY_CONTRACT[1:]:
        assert callable(getattr(family, name)), (config, name)
    assert isinstance(family.REHEARSAL_FIELDS, dict)


def test_every_configuration_is_known_here():
    """A configuration a later PR adds brings its literals to LITERALS."""
    assert set(CONFIGS) == {config for config, _, _ in LITERALS}


# (configuration, what the harness asks its family, the answer to the digit)
LITERALS = [
    ("mistral-7b", "param_count", 480_260_096),
    ("mistral-7b", "train_flops_per_token", 2_195_742_720),
    ("mistral-7b", "flash_attention_flops",
     (2 * DENSE_SCORE, 5 * DENSE_SCORE)),
    ("mixtral-8x7b", "param_count", 1_713_418_240),
    ("mixtral-8x7b", "train_flops_per_token", 3_252_903_936),
    ("mixtral-8x7b", "flash_attention_flops",
     (2 * DENSE_SCORE, 5 * DENSE_SCORE)),
    # four passes over eight layers: every count holds T x L
    ("ouro-2.6b", "param_count", 612_438_017),
    ("ouro-2.6b", "train_flops_per_token", 13_891_977_216),
    ("ouro-2.6b", "flash_attention_flops",
     (4 * 8 * 2 * LOOP_SCORE, 4 * 8 * 5 * LOOP_SCORE)),
    # one chip's share: 8 of 64 experts, an eighth of the vocabulary; the
    # kernels at 192-wide queries and keys over 128-wide values, forward
    # 192 + 128, backward 3 x 192 + 2 x 128, training 3 x (192 + 128)
    ("moonlight-16b-a3b", "param_count", 668_890_432),
    # 313,327,616 matmul parameters a token meets: attention 6 x
    # 13,762,560, the dense SwiGLU 69,206,016, shared experts 5 x
    # 17,301,504, the router 5 x 131,072, held experts 5 x 0.75 x
    # 8,650,752, the head 41,943,040
    ("moonlight-16b-a3b", "train_flops_per_token",
     6 * 313_327_616 + 6 * 3 * 320 * 16 * 4097),
    ("moonlight-16b-a3b", "flash_attention_flops",
     (6 * 320 * MLA_SCORE, 6 * 832 * MLA_SCORE)),
]


@pytest.mark.parametrize("config,what,expected", LITERALS)
def test_the_literals_hold_through_the_families(config, what, expected):
    fields = fields_of(config)
    family = bench_run.load_family(fields["family"])
    got = {
        "param_count": lambda: family.param_count(fields),
        "train_flops_per_token":
            lambda: family.train_flops_per_token(fields, SEQ),
        "flash_attention_flops":
            lambda: family.flash_attention_flops(fields, SEQ, 1),
    }[what]()
    assert got == expected
