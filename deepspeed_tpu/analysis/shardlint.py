"""shardlint driver: trace → context → rules → report.

Three entry points, all CPU-cheap (abstract evaluation only):

- :func:`lint_jaxpr` — lint any program you already traced.
- :func:`lint_engine` — trace a constructed engine's jitted train step
  (works on ``abstract_init=True`` shells whose state is
  ShapeDtypeStructs) and lint it, plus engine-level closure/donation
  audits the jaxpr alone cannot express.
- :func:`lint_config` — ds_config (+ model) → abstract engine → lint.

The registry is R1–R13 (docs/shardlint.md); R9 (rng-discipline) and R10
(reduction-order) run on every program, R11 (trace-stability) arms when
the trace driver supplies the step's traced-argument manifest — both
entry points here do — and R12/R13 (DCN rules) arm when the topology
carries DCN-tagged link metadata (hybrid meshes).
"""

from __future__ import annotations

import time
import warnings
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from .base import ERROR, WARNING, Finding, LintContext, Report, sharding_fingerprint
from .rules import run_rules


def lint_jaxpr(
    closed_jaxpr,
    *,
    mesh=None,
    arg_shardings: Optional[Dict[Any, Any]] = None,
    master_pairs: Sequence = (),
    source: str = "<jaxpr>",
    only: Optional[Sequence[str]] = None,
    hbm_budget_bytes: Optional[float] = None,
    streams: Optional[Dict[str, Any]] = None,
    hardware=None,
    link_kinds: Optional[Dict[str, str]] = None,
    donated_invars: Sequence[int] = (),
    invar_groups: Optional[Dict[str, Any]] = None,
    claims_keyfree: bool = False,
    required_traced: Sequence[str] = (),
    traced_manifest: Optional[Dict[str, Any]] = None,
) -> List[Finding]:
    """Run the rule registry over one traced program."""
    ctx = LintContext(
        closed_jaxpr=closed_jaxpr,
        mesh=mesh,
        arg_shardings=arg_shardings or {},
        master_pairs=tuple(master_pairs),
        source=source,
        hbm_budget_bytes=hbm_budget_bytes,
        streams=dict(streams or {}),
        hardware=hardware,
        link_kinds=dict(link_kinds or {}),
        donated_invars=tuple(donated_invars),
        invar_groups=dict(invar_groups or {}),
        claims_keyfree=claims_keyfree,
        required_traced=tuple(required_traced),
        traced_manifest=dict(traced_manifest or {}),
    )
    return run_rules(ctx, only=only)


# --------------------------------------------------------------- engine lint
def _leaf_sharding(leaf):
    return getattr(leaf, "sharding", None)


def _as_sds(leaf):
    """Array/ShapeDtypeStruct → ShapeDtypeStruct preserving sharding."""
    if isinstance(leaf, jax.ShapeDtypeStruct):
        return leaf
    return jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=_leaf_sharding(leaf)
    )


def _batch_sds(engine):
    cfg = engine.config
    accum = cfg.gradient_accumulation_steps
    B = cfg.train_batch_size
    S = getattr(getattr(engine.model, "config", None), "max_seq_len", None)
    if B is None or S is None:
        raise ValueError(
            "lint_engine needs a resolved train_batch_size and a model "
            "config with max_seq_len to shape the abstract batch"
        )
    sharding = engine._batch_sharding(accum_leading=True)
    shape = (accum, B // accum, S)
    sds = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)
    return {"input_ids": sds, "labels": sds}


def _flat_with_paths(tree):
    leaves, _ = jax.tree_util.tree_flatten(tree)
    paths = [
        jax.tree_util.keystr(kp)
        for kp, _ in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]
    return leaves, paths


def trace_train_step(engine):
    """(closed_jaxpr, arg_shardings, master_pairs, out_shape, meta).

    Traces ``engine._train_step`` (the body of the jitted train step —
    same program the runtime compiles) with ShapeDtypeStruct state and
    batch: abstract evaluation, nothing touches devices.

    ``meta`` carries the jit-boundary evidence the cost planner needs:
    ``invar_groups`` (state-group name → flat invar index range) and
    ``donated_invars`` (the state leaves ``_jit_train`` donates — its
    ``donate_argnums=(0, 1, 2, 3)`` covers params/opt/scale/step).
    """
    from ..models.sharding import use_topology

    state = engine.state
    params = jax.tree.map(_as_sds, state.params)
    opt_state = jax.tree.map(_as_sds, state.opt_state)
    loss_scale = state.loss_scale
    step = jax.ShapeDtypeStruct((), jnp.int32)
    batch = _batch_sds(engine)
    rng = jax.random.PRNGKey(0)

    def fn(p, o, s, st, b, r):
        return engine._train_step(p, o, s, st, b, r, None)

    args = (params, opt_state, loss_scale, step, batch, rng)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with use_topology(engine.topology):
            closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)

    flat_args, arg_paths = _flat_with_paths(args)
    invars = list(closed.jaxpr.invars)
    arg_shardings: Dict[Any, Any] = {}
    if len(flat_args) == len(invars):
        for v, leaf in zip(invars, flat_args):
            s = _leaf_sharding(leaf)
            if s is not None:
                arg_shardings[v] = s

    # master pairs: f32 params/opt leaves must round-trip at full precision
    master_pairs = []
    out_leaves = jax.tree_util.tree_leaves(out_shape)
    n_p = len(jax.tree_util.tree_leaves(params))
    n_o = len(jax.tree_util.tree_leaves(opt_state))
    if len(flat_args) == len(invars) and len(out_leaves) == len(
        closed.jaxpr.outvars
    ):
        # step outputs: (params, opt, scale, step, metrics) — same leading
        # structure as the inputs
        for i in range(n_p + n_o):
            leaf = flat_args[i]
            if leaf.dtype == jnp.float32 and out_leaves[i].dtype == jnp.float32:
                if leaf.shape == out_leaves[i].shape:
                    master_pairs.append((i, i, arg_paths[i]))

    # planner metadata: which flat invars are which state group, and which
    # the jitted step donates (donate_argnums=(0,1,2,3) — the whole state)
    n_s = len(jax.tree_util.tree_leaves(loss_scale))
    n_step = 1
    n_batch = len(jax.tree_util.tree_leaves(batch))
    bounds = [
        ("params", n_p), ("opt_state", n_o), ("loss_scale", n_s),
        ("step", n_step), ("batch", n_batch),
    ]
    invar_groups, lo = {}, 0
    for name, n in bounds:
        invar_groups[name] = (lo, lo + n)
        lo += n
    meta = (
        {
            "invar_groups": invar_groups,
            "donated_invars": tuple(range(n_p + n_o + n_s + n_step)),
        }
        if len(flat_args) == len(invars)
        else {"invar_groups": {}, "donated_invars": ()}
    )
    return closed, arg_shardings, master_pairs, out_shape, meta


def pallas_grids(jaxpr):
    """``[(kernel function, grid)]`` of every ``pallas_call`` under a jaxpr,
    in program order, the bodies of scans, remats and custom derivatives
    included: what a kernel's grid IS in the program that was traced (the
    flash kernels' walk of a causal call's live tiles, say)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["jaxpr"].debug_info.func_name,
                        tuple(eqn.params["grid_mapping"].grid)))
        for v in eqn.params.values():
            for x in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(x, "jaxpr", x)  # closed or open
                if hasattr(sub, "eqns"):
                    out += pallas_grids(sub)
    return out


def lower_train_step(engine):
    """The engine's jitted train step, lowered for its own state and batch
    shapes (abstract: nothing materializes, nothing is donated). Works on
    a live engine and on an ``abstract_init`` one; ``.compile()`` gives
    XLA's memory accounting and the compiled text (is the kernel really a
    ``tpu_custom_call``? is the collective there?)."""
    from ..models.sharding import use_topology

    state = engine.state
    # the same scope train_batch traces under: the kernels read the mesh
    # from it (a bare pallas_call cannot be partitioned by GSPMD)
    with use_topology(engine.topology):
        return engine._jit_train.lower(
            jax.tree.map(_as_sds, state.params),
            jax.tree.map(_as_sds, state.opt_state),
            state.loss_scale,
            jax.ShapeDtypeStruct((), jnp.int32),
            _batch_sds(engine),
            jax.random.PRNGKey(0),
            None,
        )


def compiled_train_memory_peak(engine):
    """``(peak_bytes, memory_analysis)`` from XLA's own accounting of
    the engine's train step (peak = argument + temp + output − alias),
    via an abstract lower + compile — nothing materializes.
    ``(None, None)`` when the backend does not report memory analysis.
    This is the ONE definition of the cross-check anchor the planner's
    peak band is measured against (tests/test_shardplan.py,
    tools/autoplan.py --check)."""
    ma = lower_train_step(engine).compile().memory_analysis()
    if not getattr(ma, "temp_size_in_bytes", 0):
        return None, None
    peak = (
        ma.argument_size_in_bytes
        + ma.temp_size_in_bytes
        + ma.output_size_in_bytes
        - ma.alias_size_in_bytes
    )
    return peak, ma


def _engine_level_findings(engine, out_shape) -> List[Finding]:
    """Closure + donation audits at the jit boundary (not jaxpr-visible)."""
    findings: List[Finding] = []
    # R2: the chain scans the step — the step's out_shardings must equal
    # the state's resting shardings leaf-for-leaf
    state_tuple = engine.state.astuple()
    for name, tree, shardings in zip(
        ("params", "opt_state", "loss_scale", "step"),
        state_tuple,
        engine._state_shardings,
    ):
        in_leaves = jax.tree_util.tree_leaves(tree)
        out_leaves = jax.tree_util.tree_leaves(shardings)
        if len(in_leaves) != len(out_leaves):
            continue
        for leaf, out_s in zip(in_leaves, out_leaves):
            fp_in = sharding_fingerprint(_leaf_sharding(leaf))
            fp_out = sharding_fingerprint(out_s)
            if fp_in is not None and fp_out is not None and fp_in != fp_out:
                findings.append(Finding(
                    rule="R2",
                    severity=ERROR,
                    message=(
                        f"{name}: resting sharding {fp_in} != step "
                        f"out_sharding {fp_out} — train_batch_chain's scan "
                        "carry is not closed over the step"
                    ),
                    where="<jit boundary>",
                ))
    # R4: every donated input buffer should be consumable by some output
    # (shape/dtype/sharding match); an unusable donation silently doubles
    # peak memory for that leaf
    out_avals = {}
    for leaf in jax.tree_util.tree_leaves(out_shape):
        key = (tuple(leaf.shape), str(leaf.dtype))
        out_avals[key] = out_avals.get(key, 0) + 1
    for name, tree in zip(("params", "opt_state", "loss_scale", "step"),
                          state_tuple):
        for leaf in jax.tree_util.tree_leaves(tree):
            key = (tuple(leaf.shape), str(jnp.dtype(leaf.dtype)))
            if out_avals.get(key, 0) > 0:
                out_avals[key] -= 1
            else:
                findings.append(Finding(
                    rule="R4",
                    severity=WARNING,
                    message=(
                        f"donated {name} leaf {key[1]}{list(key[0])} has no "
                        "matching output buffer — the donation is unusable "
                        "and peak memory holds both copies"
                    ),
                    where="<jit boundary>",
                ))
    return findings


def lint_engine(engine, only: Optional[Sequence[str]] = None,
                source: Optional[str] = None,
                hbm_budget_bytes: Optional[float] = None,
                hardware=None,
                collect_plan: bool = False) -> Report:
    """Trace + lint one engine's train step. Seconds on CPU.

    ``hbm_budget_bytes`` arms rule R6 (static OOM-before-compile check);
    ``collect_plan`` attaches the cost plan (analysis/cost) to the
    report so drivers print the per-config budget table without tracing
    twice. The engine's declared analytic streams (offload
    double-buffer, decomposed-TP rings) feed rule R8 either way.
    """
    from .cost import plan_for_context

    report = Report()
    name = source or f"engine[{type(engine).__name__}]"
    t0 = time.time()
    closed, arg_shardings, master_pairs, out_shape, meta = trace_train_step(
        engine
    )
    streams = (
        engine.analytic_streams(include_potential=True)
        if hasattr(engine, "analytic_streams")
        else {}
    )
    ctx = LintContext(
        closed_jaxpr=closed,
        mesh=engine.topology.mesh,
        arg_shardings=arg_shardings,
        master_pairs=tuple(master_pairs),
        source=name,
        hbm_budget_bytes=hbm_budget_bytes,
        streams=streams,
        hardware=hardware,
        link_kinds=dict(getattr(engine.topology, "link_kinds", None) or {}),
        donated_invars=meta["donated_invars"],
        invar_groups=meta["invar_groups"],
        # R11: the train step must consume its per-step batch — a dead
        # batch input means the program was specialized on trace-time
        # data (the manifest IS the invar-group split)
        required_traced=("batch",) if meta["invar_groups"] else (),
        traced_manifest=meta["invar_groups"],
    )
    findings = run_rules(ctx, only=only)
    for f in _engine_level_findings(engine, out_shape):
        if only is None or f.rule in only:
            f.source = name
            findings.append(f)
    report.extend(findings)
    report.add_source(name, time.time() - t0, len(findings))
    if collect_plan:
        report.plans.append(plan_for_context(ctx))
    return report


def lint_serving_config(config, model=None, topology=None,
                        only: Optional[Sequence[str]] = None,
                        source: Optional[str] = None,
                        hbm_budget_bytes: Optional[float] = None,
                        hardware=None,
                        collect_plan: bool = False) -> Report:
    """Lint a SERVING config: trace the continuous-batching engine's one
    jitted slot step abstractly (serving.trace_serving_step — params and
    the KV arena are ShapeDtypeStructs with real shardings) and run the
    same R1–R13 registry over it (R11 armed by the
    trace's traced-args manifest). The declared analytic streams (the
    per-step KV-arena traffic) feed the planner and rule R8 exactly like
    the training engines' streams."""
    from ..config import DeepSpeedConfig
    from ..comm.topology import MeshTopology, ParallelDims
    from ..serving.engine import trace_serving_step
    from .cost import plan_for_context
    from .rules import run_rules

    if model is None:
        raise ValueError("lint_serving_config requires a model (the step "
                         "program is model-shaped)")
    ds = (
        config if isinstance(config, DeepSpeedConfig)
        else DeepSpeedConfig(config)
    )
    tp = max(int(ds.tensor_parallel.tp_size), 1)
    # MoE serving configs lint on the ep mesh they would serve on (the
    # expert exchange only exists in the traced program when the ep axis
    # does) — serving_ep_size is the ONE moe.ep_size clamp, shared with
    # trace_serving_step
    from ..serving.engine import serving_ep_size

    ep = serving_ep_size(ds.moe, getattr(model, "config", None))
    if topology is None:
        topology = MeshTopology(
            dims=ParallelDims(tp=tp, ep=ep),
            devices=jax.devices()[:tp * ep],
        )
    report = Report()
    name = source or "serving"
    t0 = time.time()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        closed, arg_shardings, streams, meta = trace_serving_step(
            model, ds, topology
        )
    ctx = LintContext(
        closed_jaxpr=closed,
        mesh=topology.mesh,
        arg_shardings=arg_shardings,
        source=name,
        hbm_budget_bytes=hbm_budget_bytes,
        streams=streams,
        hardware=hardware,
        link_kinds=dict(getattr(topology, "link_kinds", None) or {}),
        required_traced=meta.get("required_traced", ()),
        traced_manifest=meta.get("traced_manifest", {}),
    )
    findings = run_rules(ctx, only=only)
    report.extend(findings)
    report.add_source(name, time.time() - t0, len(findings))
    if collect_plan:
        report.plans.append(plan_for_context(ctx))
    return report


def lint_config(config, model=None, topology=None,
                only: Optional[Sequence[str]] = None,
                source: Optional[str] = None,
                hbm_budget_bytes: Optional[float] = None,
                hardware=None,
                collect_plan: bool = False) -> Report:
    """Build an abstract engine (no state materialization) and lint it.

    ``config`` is anything DeepSpeedConfig accepts (dict / path). The
    caller owns comm state: an already-initialized topology is reused,
    else one is built from the config exactly like training would.
    Configs whose "serving" section is enabled lint the serving engine's
    slot step instead of a train step (:func:`lint_serving_config`).
    """
    import deepspeed_tpu
    from ..config import DeepSpeedConfig

    if model is None:
        raise ValueError("lint_config requires a model (the step program "
                         "is model-shaped); tools/shardlint.py picks one "
                         "from the config when run as a CLI")
    ds = (
        config if isinstance(config, DeepSpeedConfig)
        else DeepSpeedConfig(config)
    )
    if ds.serving.enabled:
        return lint_serving_config(
            ds, model=model, topology=topology, only=only, source=source,
            hbm_budget_bytes=hbm_budget_bytes, hardware=hardware,
            collect_plan=collect_plan,
        )
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=config, topology=topology, abstract_init=True
    )
    try:
        return lint_engine(
            engine, only=only, source=source,
            hbm_budget_bytes=hbm_budget_bytes, hardware=hardware,
            collect_plan=collect_plan,
        )
    finally:
        engine.destroy()
