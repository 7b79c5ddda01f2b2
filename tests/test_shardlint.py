"""shardlint: seeded-bug corpus (ISSUE 2 acceptance) + rule unit tests.

The corpus (tests/analysis_corpus/fixtures.py) reintroduces the repo's
historical hazard classes as traceable programs; every hazard must be
flagged by its rule and every clean twin must lint clean.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as comm
from deepspeed_tpu.analysis import lint_engine, lint_jaxpr
from deepspeed_tpu.analysis.rules.topology import check_permutation
from deepspeed_tpu.models import gpt2

from analysis_corpus import fixtures as fx

pytestmark = pytest.mark.shardlint


@pytest.mark.parametrize("build", fx.HAZARDS, ids=lambda f: f.__name__)
def test_corpus_hazard_is_flagged(build, devices8):
    closed, kw, rule = build()
    findings = lint_jaxpr(closed, source=build.__name__, **kw)
    assert any(f.rule == rule and f.severity == "error" for f in findings), (
        f"{build.__name__}: expected a {rule} finding, got "
        f"{[f.format() for f in findings]}"
    )


@pytest.mark.parametrize("build", fx.CLEAN_TWINS, ids=lambda f: f.__name__)
def test_corpus_clean_twin_passes(build, devices8):
    closed, kw, _rule = build()
    findings = lint_jaxpr(closed, source=build.__name__, **kw)
    assert findings == [], [f.format() for f in findings]


def test_rule_subset_selection(devices8):
    closed, kw, _ = fx.missing_psum_grads()
    assert lint_jaxpr(closed, only=["R3"], **kw) == []
    assert lint_jaxpr(closed, only=["R1"], **kw)


def test_check_permutation_catalog():
    # legal: full ring, pipeline neighbor chain, empty perm
    assert check_permutation([(0, 1), (1, 2), (2, 3), (3, 0)], 4) == []
    assert check_permutation([(0, 1), (1, 2), (2, 3)], 4) == []
    assert check_permutation([], 4) == []
    # illegal shapes, one problem class each
    assert check_permutation([(0, 5)], 4)          # out of range
    assert check_permutation([(0, 1), (0, 2)], 4)  # dup src
    assert check_permutation([(0, 1), (2, 1)], 4)  # dup dst
    assert check_permutation([(1, 1)], 4)          # self-loop
    assert check_permutation([(0, 1), (1, 0), (2, 3), (3, 2)], 4)  # 2 rings
    assert check_permutation([(0, 1), (1, 0)], 4)  # partial ring
    assert check_permutation([(0, 1), (1, 0), (2, 0)], 4)  # ring + stray


def test_read_after_donate_pjit(devices8):
    """R4(b): a value consumed after an inner jit donated it."""
    import warnings

    g = jax.jit(lambda a: a + 1.0, donate_argnums=0)

    def prog(x):
        y = g(x)
        return y + x * 2.0

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        closed = jax.make_jaxpr(prog)(jnp.zeros(4))
    findings = lint_jaxpr(closed, source="pjit-donate")
    assert any(f.rule == "R4" for f in findings)


# ---------------------------------------------------------- engine linting
BASE_CFG = {
    "train_batch_size": 16,
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
    "bf16": {"enabled": True},
    "gradient_clipping": 1.0,
}


def _abstract_engine(cfg, model=None):
    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(
        model=model or gpt2("gpt2-tiny", vocab_size=128, max_seq_len=16),
        config=dict(cfg),
        abstract_init=True,
    )
    return engine


@pytest.mark.parametrize("stage", [0, 3])
def test_engine_lint_clean_across_zero_stages(stage, devices8):
    engine = _abstract_engine(
        dict(BASE_CFG, zero_optimization={"stage": stage})
    )
    report = lint_engine(engine)
    assert report.ok and not report.findings, report.format()


def test_engine_lint_clean_bucketed_offload_double_buffer(devices8):
    engine = _abstract_engine(dict(
        BASE_CFG,
        zero_optimization={
            "stage": 3,
            "offload_optimizer": {"device": "cpu"},
            "offload_double_buffer": True,
        },
    ))
    assert engine._bucketed_opt is not None
    assert engine._bucketed_opt.double_buffer
    report = lint_engine(engine)
    assert report.ok and not report.findings, report.format()


def test_abstract_engine_never_materializes_and_refuses_to_step(devices8):
    engine = _abstract_engine(dict(BASE_CFG, zero_optimization={"stage": 3}))
    leaves = jax.tree_util.tree_leaves(engine.state.params)
    assert leaves and all(
        isinstance(leaf, jax.ShapeDtypeStruct) for leaf in leaves
    )
    assert all(leaf.sharding is not None for leaf in leaves)
    batch = {"input_ids": np.zeros((16, 16), np.int32)}
    with pytest.raises(RuntimeError, match="abstract_init"):
        engine.train_batch(batch=batch)
    with pytest.raises(RuntimeError, match="abstract_init"):
        engine.train_batch_chain(batch=batch, steps=2)
    engine.destroy()  # must not raise on ShapeDtypeStruct state


def test_engine_lint_flags_planted_out_sharding_drift(devices8):
    """The engine-level R2 audit: a step whose out_shardings disagree with
    the resting state shardings (the chain-carry drift class) is caught
    without tracing anything."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    engine = _abstract_engine(dict(BASE_CFG, zero_optimization={"stage": 3}))
    bad = jax.tree.map(
        lambda s: NamedSharding(s.mesh, P()),
        engine._state_shardings[0],
    )
    engine._state_shardings = (bad, *engine._state_shardings[1:])
    report = lint_engine(engine)
    assert any(f.rule == "R2" for f in report.findings), report.format()


def test_lint_speed_budget(devices8):
    """ISSUE 2 acceptance: full analysis of one engine config < 30 s on
    CPU — measured on the heaviest shipped leg (1.5B double-buffered
    offload)."""
    import time

    from deepspeed_tpu.analysis.targets import lint_targets

    name, model, cfg = lint_targets(len(jax.devices()))[-1]
    assert name == "bench-1b-offload-db"
    comm.destroy_process_group()
    t0 = time.time()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=cfg, abstract_init=True
    )
    report = lint_engine(engine, source=name)
    elapsed = time.time() - t0
    assert report.ok and not report.findings, report.format()
    assert elapsed < 30.0, f"lint took {elapsed:.1f}s (budget 30s)"
