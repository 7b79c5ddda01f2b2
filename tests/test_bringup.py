"""What PR 22's bring-up rests on, checked without the chip: where the
compile cache goes, that native helpers are keyed on their source, that
chip_smoke.py refuses to run (or to say ok) without a TPU, that launcher
parents stay off jax, and that the two lowering helpers the smoke reads
compiled programs through work on live engines."""

import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.models import llama, mixtral
from deepspeed_tpu.serving import Request
from deepspeed_tpu.utils import compile_cache, native_build

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ compile cache
@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path,
                                           restore_cache_dir):
    """JAX_COMPILATION_CACHE_DIR set: that directory, and no directory is
    set in code."""
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert seen == []


def test_compile_cache_default_is_fixed_path_in_checkout(monkeypatch,
                                                         restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path  # no pid, no time


def test_no_cache_path_under_tmp_in_entry_scripts():
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        assert "/tmp" not in f.read()


# ------------------------------------------------------------- native build
C_SRC = "extern \"C\" int answer() { return %d; }\n"


def test_native_build_is_keyed_on_source_content(tmp_path):
    import ctypes

    src = tmp_path / "thing.cpp"
    src.write_text(C_SRC % 41)
    first = native_build.build_shared_lib(str(src), "thing")
    assert ctypes.CDLL(first).answer() == 41
    # same content, any mtime: the same object, not rebuilt
    stamp = os.path.getmtime(first)
    os.utime(src, (0, 0))
    assert native_build.build_shared_lib(str(src), "thing") == first
    assert os.path.getmtime(first) == stamp
    # new content with an OLDER mtime than the binary: a new object
    src.write_text(C_SRC % 42)
    os.utime(src, (0, 0))
    second = native_build.build_shared_lib(str(src), "thing")
    assert second != first
    assert ctypes.CDLL(second).answer() == 42
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]


def test_native_build_failure_raises_and_leaves_nothing(tmp_path):
    src = tmp_path / "broken.cpp"
    src.write_text("this is not C++\n")
    with pytest.raises(subprocess.CalledProcessError):
        native_build.build_shared_lib(str(src), "broken")
    assert os.listdir(tmp_path) == ["broken.cpp"]


def test_no_binary_is_tracked_or_trusted_by_mtime():
    tracked = subprocess.run(
        ["git", "ls-files", "*.so"], cwd=REPO, capture_output=True, text=True
    ).stdout.split()
    assert tracked == []
    for rel in ("deepspeed_tpu/ops/aio.py",
                "deepspeed_tpu/data_pipeline/indexed_dataset.py"):
        with open(os.path.join(REPO, rel)) as f:
            assert "getmtime" not in f.read(), rel


# --------------------------------------------------------------- chip_smoke
def _smoke(*args, cwd=REPO):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_chip_smoke_refuses_without_a_tpu():
    proc = _smoke()
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""  # no phase ran, no result printed
    assert "no TPU here" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_chip_smoke_rehearsal_never_prints_ok():
    proc = _smoke("--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert '"ok"' not in proc.stdout
    assert '"rehearsal": "passed"' in last and '"platform": "cpu"' in last
    assert "REHEARSAL on the CPU" in proc.stdout


def test_chip_smoke_serve_depth_counts_the_float32_draw():
    smoke = _load(os.path.join(REPO, "chip_smoke.py"), "chip_smoke_mod")
    from deepspeed_tpu.models.mixtral import mixtral_config

    cfg = mixtral_config("mixtral-8x7b")
    kv = 80 << 20
    # 16 GiB: four layers of weights would fit, the draw allows two
    assert smoke.serve_depth(cfg, 16 * 2**30, kv) == 2
    assert smoke.serve_depth(cfg, 32 * 2**30, kv) > 2
    assert smoke.serve_depth(cfg, 2 * 2**30, kv) == 0


# ------------------------------------------------ parents stay off jax
@pytest.mark.parametrize("rel", ["tools/elastic_run.py",
                                 "__graft_entry__.py"])
def test_launcher_parent_does_not_import_jax(rel):
    """Importing the launcher (what its parent process does before it
    spawns workers) must not pull jax in: a parent that has touched jax
    holds the chip its children need."""
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('m', {rel!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "assert 'jax' not in sys.modules, 'jax imported at load'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_elastic_oracle_parent_has_no_jax_probe():
    with open(os.path.join(REPO, "tools", "elastic_run.py")) as f:
        src = f.read()
    oracle = src[src.index("def run_oracle"):src.index("def main")]
    assert "import jax" not in oracle


# ------------------------------------------------------ lowering helpers
def test_lower_train_step_on_a_live_sharded_engine(devices8):
    """The helper chip_smoke reads the compiled train step through: it
    lowers under the engine's topology (the kernels read the mesh from
    that scope), does not disturb the live state, and the compiled step
    of a ZeRO-3 engine has its collectives."""
    from deepspeed_tpu.analysis.shardlint import lower_train_step

    model = llama("llama-tiny", vocab_size=256, max_seq_len=128,
                  hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
                  intermediate_size=128)
    topo = MeshTopology(dims=ParallelDims(dp=4), devices=jax.devices()[:4])
    engine, *_ = deepspeed_tpu.initialize(
        model=model, topology=topo, config={
            "train_batch_size": 4,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 3,
                                  "stage3_param_persistence_threshold": 1},
            "tpu_kernels": {"flash_attention": True},
        })
    batch = {"input_ids": np.random.RandomState(0).randint(0, 256, (4, 128))}
    first = float(engine.train_batch(batch=batch))
    traces = engine.step_traces
    compiled = lower_train_step(engine).compile()
    assert engine.step_traces == traces or engine.step_traces == traces + 1
    assert compiled.memory_analysis() is not None
    assert "all-gather" in compiled.as_text()
    second = float(engine.train_batch(batch=batch))  # state still usable
    assert np.isfinite(first) and second < first


def test_serving_lower_step_matches_the_served_program():
    model = mixtral("mixtral-tiny", vocab_size=64, max_seq_len=64,
                    hidden_size=32, num_layers=2, num_heads=4,
                    num_kv_heads=2, intermediate_size=64, num_experts=4,
                    moe_top_k=2)
    srv = deepspeed_tpu.init_serving(
        model, serving={"max_slots": 2, "token_budget": 8, "max_tokens": 32,
                        "paged": True, "page_size": 8},
        dtype=jnp.float32, rng=jax.random.PRNGKey(0),
    )
    st = srv.submit(Request(request_id="a", prompt=np.arange(5),
                            max_new_tokens=3))
    srv.run_until_idle()
    assert len(st.tokens) == 3 and srv.step_traces == 1
    lowered = srv.lower_step()
    assert srv.step_traces == 1  # lowering is not a recompile of the step
    text = lowered.compile().as_text()
    assert "while" in text or "fusion" in text  # a real compiled program
    # the arena was not donated away by lowering: the engine still serves
    st2 = srv.submit(Request(request_id="b", prompt=np.arange(5),
                             max_new_tokens=3))
    srv.run_until_idle()
    assert list(st2.tokens) == list(st.tokens) and srv.step_traces == 1
