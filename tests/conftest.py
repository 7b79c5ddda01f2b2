"""Test harness: force a virtual 8-device CPU mesh before jax initialises.

Mirrors the reference's unit-test strategy (tests/unit) of running
world_size>1 logic on a single box — here via XLA host-platform devices.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

# jax may already be imported when conftest runs (a plugin, a -p option), and
# then the env vars alone are too late — set the config flags as well.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_threefry_partitionable", True)

import json  # noqa: E402

import pytest  # noqa: E402

# ---- shardlint suite capture -----------------------------------------------
# Every engine the test suite constructs registers its (config, model) here
# (deduped); tests/test_shardlint_suite.py re-builds each as an abstract
# engine and lints it — "lint every engine config already constructed by
# the test suite" without re-running any real compute.
SHARDLINT_CAPTURE = []  # [(config_json, model, topology)]
_SHARDLINT_SEEN = set()


def _install_shardlint_capture():
    from deepspeed_tpu.runtime import engine as _engine_mod

    orig = _engine_mod.TpuEngine.__init__

    def spy(self, model, config, topology, **kw):
        out = orig(self, model=model, config=config, topology=topology, **kw)
        # record only AFTER a successful construction: configs that tests
        # build to be rejected mid-__init__ must not poison the registry
        if not kw.get("abstract_init"):
            try:
                key = (
                    json.dumps(config.raw, sort_keys=True, default=str),
                    str(getattr(model, "config", None)),
                    str(topology),
                )
                if key not in _SHARDLINT_SEEN:
                    _SHARDLINT_SEEN.add(key)
                    SHARDLINT_CAPTURE.append((config.raw, model, topology))
            except Exception:  # noqa: BLE001 — capture must never break a test
                pass
        return out

    _engine_mod.TpuEngine.__init__ = spy


_install_shardlint_capture()


@pytest.fixture(autouse=True)
def _reset_comm_state():
    yield
    import deepspeed_tpu.comm as comm

    comm.destroy_process_group()
    comm.collectives.clear_comm_hooks()


@pytest.fixture
def devices8():
    ds = jax.devices()
    assert len(ds) == 8, f"expected 8 virtual devices, got {len(ds)}"
    return ds
