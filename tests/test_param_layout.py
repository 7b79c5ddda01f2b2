"""The serve step reads its weights in the layout they are held in (ISSUE 45).

The step is compiled with its parameter leaves' layouts left to the compiler
(``compiler_param_formats``), the engine reads what was chosen off the ONE
executable and re-lays, once and in one donated call, the leaves held in
another layout. On the CPU the compiler's choice is the held layout, so the
tests that need a leaf to move force one: either the choice (the step is
compiled for a transposed ``wq`` / ``wk`` / ``wv``, as the chip's compiler
chooses) or the holder (the leaf arrives transposed). The oracle is the same
engine with nothing forced.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

import deepspeed_tpu
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import llama, mellum, minicpm
from deepspeed_tpu.serving import Request, ServingEngine
from deepspeed_tpu.serving import engine as engine_mod

F32 = jnp.float32
SERVING = dict(max_slots=3, token_budget=16, max_tokens=96, paged=True,
               page_size=16)
# [L, D, H * hd] stacks, the contraction dimension made minor: what the
# chip's compiler chooses for the q / k / v projections
TRANSPOSED = Layout(major_to_minor=(0, 2, 1))
QKV = ("wq", "wk", "wv")

FAMILIES = {
    "dense": lambda: llama(
        "llama-tiny", vocab_size=128, max_seq_len=128, hidden_size=32,
        num_layers=2, num_heads=4, num_kv_heads=2, intermediate_size=64),
    "mellum_shaped": lambda: mellum("mellum-tiny", initializer_range=0.2),
    "minicpm_sala_shaped": lambda: minicpm("minicpm-sala-tiny",
                                           initializer_range=0.1),
}


def is_qkv(path) -> bool:
    return getattr(path[-1], "key", None) in QKV


def fresh_params(model):
    return model.init(jax.random.PRNGKey(3), dtype=F32)


def inference(model, params, **kw):
    return deepspeed_tpu.init_inference(
        model, params=params, dtype=F32, max_tokens=96, **kw)


def build(model, params, steptrace=None, **kw):
    return ServingEngine(engine=inference(model, params, **kw),
                         serving=dict(SERVING), steptrace=steptrace)


def serve(srv, seed=0, n=3):
    rng = np.random.default_rng(seed)
    vocab = srv.config.vocab_size
    states = [srv.submit(Request(
        request_id=f"r{seed}.{i}", max_new_tokens=6, temperature=0.0,
        prompt=rng.integers(0, vocab, size=int(rng.integers(5, 40)))))
        for i in range(n)]
    srv.run_until_idle()
    return [list(st.tokens) for st in states]


def force_the_choice(monkeypatch, names=QKV):
    """The compiler 'chooses' the transposed layout for the named stacks:
    the step is compiled for it, as on the chip."""
    auto = engine_mod.compiler_param_formats

    def chosen(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, a, f: (Format(TRANSPOSED, a.sharding)
                                if getattr(path[-1], "key", None) in names
                                else f),
            params, auto(params))

    monkeypatch.setattr(engine_mod, "compiler_param_formats", chosen)


def hold_transposed(params):
    """The named stacks arrive in the transposed layout (same values)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: (jax.device_put(a, Format(TRANSPOSED, a.sharding))
                         if is_qkv(path) else a), params)


def layouts(params):
    return {jax.tree_util.keystr(path): a.format.layout.major_to_minor
            for path, a in jax.tree_util.tree_flatten_with_path(params)[0]}


# ---------------------------------------------------------------------------
# (a) the compiler's choice is the held layout: nothing moves
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", FAMILIES)
def test_nothing_is_relaid_where_the_choice_is_the_held_layout(family):
    model = FAMILIES[family]()
    eng = inference(model, fresh_params(model))
    params = eng.params
    srv = ServingEngine(engine=eng, serving=dict(SERVING))
    assert (srv.param_layout, srv.param_layout_reason) == ("compiled", None)
    d, snap = srv.describe(), srv.metrics.snapshot()
    assert d["param_layout"] == "compiled"
    for view in (d, snap):
        assert view["relaid_param_leaves"] == 0
        assert view["relaid_param_bytes"] == 0
        assert view["param_relayout_s"] == 0.0
    # the very arrays the inference engine held
    assert srv.engine.params is params
    assert not any(a.is_deleted() for a in jax.tree.leaves(params))
    assert srv.step_traces == 1  # compiled where it was built
    assert all(len(t) == 6 for t in serve(srv))
    assert srv.step_traces == 1


# ---------------------------------------------------------------------------
# (b) a leaf moves: same tokens, same tree, generate still runs
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("moved_by", ["the_compilers_choice", "the_holder"])
@pytest.mark.parametrize("family", FAMILIES)
def test_relaid_leaves_serve_the_parent_forms_tokens(family, moved_by,
                                                     monkeypatch):
    model = FAMILIES[family]()
    oracle = build(model, fresh_params(model))
    want = serve(oracle)
    prompt = np.arange(7, dtype=np.int32)[None]
    # (a model with mixers has no contiguous arena: generate refuses it, on
    # either form)
    lockstep = not model.config.mixer_types
    if lockstep:
        want_gen = np.asarray(oracle.engine.generate(prompt, max_new_tokens=5))
    was = jax.tree.map(np.asarray, oracle.engine.params)

    params = fresh_params(model)
    if moved_by == "the_compilers_choice":
        force_the_choice(monkeypatch)
        arrives, ends = tuple(range(3)), TRANSPOSED.major_to_minor
    else:
        params = hold_transposed(params)
        arrives, ends = TRANSPOSED.major_to_minor, tuple(range(3))
    n_qkv = sum(is_qkv(path) for path, _ in
                jax.tree_util.tree_flatten_with_path(params)[0])
    assert all(lay == arrives for name, lay in layouts(params).items()
               if name.endswith(("['wq']", "['wk']", "['wv']")))
    eng = inference(model, params)
    params = eng.params  # (committed to the device: other array objects)
    srv = ServingEngine(engine=eng, serving=dict(SERVING))
    assert srv.param_layout == "compiled"
    assert srv.describe()["relaid_param_leaves"] == n_qkv
    snap = srv.metrics.snapshot()
    assert snap["relaid_param_leaves"] == n_qkv
    assert snap["relaid_param_bytes"] == sum(
        a.nbytes for path, a in jax.tree_util.tree_flatten_with_path(
            srv.engine.params)[0] if is_qkv(path))
    assert snap["param_relayout_s"] > 0
    # the moved leaves are in the layout the step was compiled for, the
    # others the arrays that came; tree, shapes, dtypes and values as before
    now = srv.engine.params
    assert jax.tree.structure(now) == jax.tree.structure(was)
    for (path, a), (_, b), (_, mine) in zip(
            *(jax.tree_util.tree_flatten_with_path(t)[0]
              for t in (now, was, params))):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
        np.testing.assert_array_equal(np.asarray(a), b)
        if is_qkv(path):
            assert a.format.layout.major_to_minor == ends
        else:
            assert a is mine
    assert serve(srv) == want
    assert srv.step_traces == 1
    # the lockstep engine's own jit takes the arrays in the layout they have
    if lockstep:
        got_gen = np.asarray(srv.engine.generate(prompt, max_new_tokens=5))
        np.testing.assert_array_equal(got_gen, want_gen)
    else:
        with pytest.raises(DeepSpeedConfigError, match="contiguous KV arena"):
            srv.engine.generate(prompt, max_new_tokens=5)


def test_weights_replaced_after_construction_are_relaid_at_the_next_step(
        monkeypatch):
    """``engine.params = other weights`` (the benchmark's --check-seeds, a
    checkpoint): the executable's layouts are met before its next call."""
    model = FAMILIES["dense"]()
    other = jax.tree.map(lambda a: a * 1.5, fresh_params(model))
    want = serve(build(model, jax.tree.map(jnp.copy, other)), seed=1)
    force_the_choice(monkeypatch)
    srv = build(model, fresh_params(model))
    assert serve(srv, seed=5) != want
    srv.engine.params = other
    assert serve(srv, seed=1) == want
    assert srv.metrics.snapshot()["relaid_param_leaves"] == 3
    assert srv.engine.params is not other and srv.step_traces == 1
    adopted = srv.engine.params
    serve(srv, seed=2)
    assert srv.engine.params is adopted  # once, not a step
    # the engine keeps no second set alive: whoever replaces the weights
    # frees the old ones first (the chip holds one set)
    ref = weakref.ref(adopted["layers"]["attn"]["wq"])  # (a re-laid one)
    del adopted
    srv.engine.params = None
    gc.collect()
    assert ref() is None


# ---------------------------------------------------------------------------
# (c) the re-lay donates: no leaf is held twice
# ---------------------------------------------------------------------------
def live_bytes() -> int:
    """Bytes of the live device buffers, each once however many arrays
    view it (a tree committed to the device it is on shares its buffers),
    shard by shard: an earlier test file of the same worker may have left
    an array alive that is sharded over a mesh, which has no one pointer."""
    gc.collect()
    return sum({s.data.unsafe_buffer_pointer(): s.data.nbytes
                for a in jax.live_arrays() if not a.is_deleted()
                for s in a.addressable_shards}.values())


@pytest.mark.parametrize("family", ["dense", "minicpm_sala_shaped"])
def test_the_relay_donates_and_no_leaf_is_held_twice(family, monkeypatch):
    model = FAMILIES[family]()

    def engine_bytes():
        before = live_bytes()
        params = fresh_params(model)  # the caller keeps its tree, as a
        srv = build(model, params)    # benchmark does until the engine is up
        return srv, params, live_bytes() - before

    srv, params, plain = engine_bytes()
    assert srv.describe()["relaid_param_leaves"] == 0
    del srv, params
    force_the_choice(monkeypatch)
    srv, params, relaid = engine_bytes()
    assert srv.describe()["relaid_param_leaves"] > 0
    assert relaid == plain
    for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        assert a.is_deleted() == is_qkv(path)
    assert not any(a.is_deleted() for a in jax.tree.leaves(srv.engine.params))


def test_leaves_that_do_not_fit_together_go_in_as_few_calls_as_fit(
        monkeypatch):
    """Largest first, first fit: ``wq`` ([2, 32, 32]) alone, ``wk`` and
    ``wv`` ([2, 32, 16]) together in what it left; a leaf larger than the
    room goes alone all the same. The span round the calls says so."""
    model = FAMILIES["dense"]()
    want = serve(build(model, fresh_params(model)))
    force_the_choice(monkeypatch)
    wq = 2 * 32 * 32 * 4
    for free, calls in ((wq, 2), (wq // 4, 3), (10 * wq, 1)):
        monkeypatch.setattr(engine_mod, "_free_device_bytes",
                            lambda device, free=free: free)
        srv = build(model, fresh_params(model), steptrace={"enabled": True})
        span = [s for s in srv.tracer.spans  # (one registry a process)
                if s["name"] == "serve/param_relayout"][-1]
        assert span["args"] == dict(leaves=3, bytes=2 * wq, calls=calls)
        assert span["cat"] == "serve" and span["t1"] > span["t0"]
        assert serve(srv) == want


# ---------------------------------------------------------------------------
# (d) a mesh that cannot take the choice keeps the held layouts
# ---------------------------------------------------------------------------
def test_a_mesh_that_refuses_the_choice_serves_from_the_held_layouts(
        monkeypatch):
    model = FAMILIES["dense"]()
    topo = MeshTopology(dims=ParallelDims(tp=2), devices=jax.devices()[:2])
    oracle = build(model, fresh_params(model), topology=topo)
    assert oracle.param_layout == "compiled"  # the CPU mesh takes it
    want = serve(oracle)
    auto = engine_mod.compiler_param_formats

    def refuses_sharded_leaves(params):
        if any(len(a.sharding.device_set) > 1 for a in jax.tree.leaves(params)):
            raise NotImplementedError(
                "Layout.AUTO is refused for a leaf sharded over a mesh")
        return auto(params)

    monkeypatch.setattr(engine_mod, "compiler_param_formats",
                        refuses_sharded_leaves)
    params = fresh_params(model)
    srv = build(model, params, topology=topo)
    assert srv.param_layout == "held"
    assert "NotImplementedError" in srv.param_layout_reason
    assert "refused for a leaf sharded" in srv.describe()["param_layout_reason"]
    assert srv.metrics.snapshot()["relaid_param_leaves"] == 0
    assert srv.step_traces == 1
    assert serve(srv) == want
    assert srv.step_traces == 1
    # one device takes the same patched choice: the refusal is the mesh's
    assert build(model, fresh_params(model)).param_layout == "compiled"
