"""Hardware envelope the planner prices programs against.

One dataclass, per-TPU-generation defaults (the one table chip_smoke.py
reads as well), env-var overrides so a measured run and its shardplan
prediction price the same machine:

- ``DSTPU_TPU_GEN``          chip generation ("v4"/"v5e"/"v5p"/"v6e",
                             or "cpu" for the host-mesh envelope)
- ``BENCH_HOST_BW_GBS``      host<->HBM DMA link, GB/s (offload stream)
- ``BENCH_ICI_BW_GBS``       per-link ICI bandwidth, GB/s (ring hops)
- ``BENCH_DCN_BW_GBS``       per-device inter-pod DCN bandwidth, GB/s
                             (hybrid-mesh hops over DCN-tagged axes)
- ``SHARDPLAN_HBM_GB``       per-device HBM capacity budget override

Everything is per *device*: the planner's byte and flop counts are
per-device too, so seconds fall straight out.

When no generation is pinned and the active jax backend is the CPU (the
lint/test/CI mesh), detection selects the ``cpu`` row — a
deliberately rough envelope of one virtual host device on a shared
8-device mesh, calibrated against measured 410M-family steps so the
drift ledger (:mod:`.drift`) compares a CPU prediction with a CPU wall
clock instead of pricing the host like a v5e. A generation or ``device_kind``
the table has no row for is an error, never a default: a number divided by
a guessed peak is worse than no number.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

_GIB = float(1 << 30)

# (bf16 peak flops, HBM bytes, HBM GB/s) per generation, the published
# spec numbers.
# The "cpu" row is the virtual-host-device envelope: ~3 GF/s effective
# per device on a contended 8-device host mesh (measured, see
# docs/autotuning.md "Drift bands"), 16 GiB as a neutral budget column.
_GEN_TABLE = {
    "v4": (275e12, 32 * _GIB, 1228e9),
    "v5e": (197e12, 16 * _GIB, 819e9),
    "v5p": (459e12, 95 * _GIB, 2765e9),
    "v6e": (918e12, 32 * _GIB, 1640e9),
    "cpu": (3e9, 16 * _GIB, 3e9),
}

# per-generation (ici GB/s, host-DMA GB/s, dcn GB/s) defaults when the
# BENCH_*_BW_GBS overrides are unset; TPU gens share the historical 45/32
# numbers. The DCN figure is deliberately conservative: ~25 Gbit/s of
# per-device share on the inter-pod data-center network (a 4x-NIC host
# divided over its chips), an order of magnitude under any ICI link —
# the gap that makes the 2-hop hierarchical forms win.
_LINK_TABLE = {"cpu": (1.0, 3.0, 0.25)}
_LINK_DEFAULT = (45.0, 32.0, 3.125)


def gen_defaults(gen: str) -> Dict[str, float]:
    """The raw table row for one generation (the constants the drift
    ledger's recalibration suggestion talks about)."""
    if gen not in _GEN_TABLE:
        raise ValueError(
            f"hardware: no peak-table row for generation {gen!r} "
            f"(known: {sorted(_GEN_TABLE)})"
        )
    flops, hbm, hbm_bw = _GEN_TABLE[gen]
    ici, host, dcn = _LINK_TABLE.get(gen, _LINK_DEFAULT)
    return {"peak_flops": flops, "hbm_bytes": hbm, "hbm_bw": hbm_bw,
            "ici_bw": ici * 1e9, "host_bw": host * 1e9,
            "dcn_bw": dcn * 1e9}


# device_kind substrings, checked IN ORDER ("v5p" must win before the
# bare "v5" fallback; the lite parts report "TPU v5 lite"/"TPU v6 lite"
# or the short "v5e"/"v6e" spelling depending on the runtime version)
_DEVICE_KIND_GENS: Tuple[Tuple[str, str], ...] = (
    ("v6e", "v6e"),
    ("v6 lite", "v6e"),
    ("v6", "v6e"),
    ("v5e", "v5e"),
    ("v5 lite", "v5e"),
    ("v5litepod", "v5e"),
    ("v5p", "v5p"),
    ("v5", "v5p"),
    ("v4", "v4"),
)


def gen_from_device_kind(kind: Optional[str]) -> Optional[str]:
    """Map ``jax.devices()[0].device_kind`` to a `_GEN_TABLE` generation.

    Returns None for kinds the table has no row for (v2/v3, emulators,
    future chips); :func:`detect_gen` turns that into an error."""
    if not kind:
        return None
    k = str(kind).lower()
    for sub, gen in _DEVICE_KIND_GENS:
        if sub in k:
            return gen
    return None


def detect_gen() -> str:
    """The generation `HardwareModel.detect()` prices: the
    ``DSTPU_TPU_GEN`` env pin wins; a CPU backend selects the ``cpu``
    envelope; any other backend reads the real ``device_kind``, and a kind
    the table does not know raises (add a `_GEN_TABLE` row and a
    `_DEVICE_KIND_GENS` entry for the new chip). A backend that fails to
    initialise raises what jax raises."""
    gen = os.environ.get("DSTPU_TPU_GEN")
    if gen:
        return gen
    import jax

    if jax.default_backend() == "cpu":
        return "cpu"
    kind = jax.devices()[0].device_kind
    g = gen_from_device_kind(kind)
    if g is None:
        raise ValueError(
            f"hardware: device_kind {kind!r} is not in the peak table — "
            "refusing to price it as some other chip (pin DSTPU_TPU_GEN, or "
            "add its _GEN_TABLE row and _DEVICE_KIND_GENS entry)"
        )
    return g


# ---------------------------------------------------------------------------
# Per-topology knob default tables (tools/autoplan.py --campaign).
#
# A campaign measures the knob lattice on real hardware and emits a table
# of measured-best defaults keyed by (gen, mesh topology, model class).
# This module SHIPS that table as data (knob_defaults.json next to this
# file — empty until the first on-chip campaign lands its rows) and owns
# the lookup; config.resolve_auto_knobs() consults it whenever a knob is
# "auto" and applies the staleness gate (drift.check_pair on each entry's
# recorded evidence). The table is measured evidence, reviewed and
# committed like a recalibration — the resolver never writes it.
# ---------------------------------------------------------------------------

KNOB_TABLE_ENV = "DSTPU_KNOB_TABLE"
_PACKAGED_KNOB_TABLE = os.path.join(os.path.dirname(__file__),
                                    "knob_defaults.json")
# measurement-transfer chain: a gen with no measured row falls back to
# the nearest measured generation's row before giving up (v5e is the
# fleet's workhorse and the historical pricing default); "cpu" rows are
# plumbing evidence and never stand in for chips
GEN_FALLBACKS: Dict[str, Tuple[str, ...]] = {
    "v6e": ("v5e",),
    "v5p": ("v5e",),
    "v4": ("v5e",),
    "cpu": (),
}

_AXIS_ORDER = ("dp", "fsdp", "pp", "sp", "ep", "tp")


def topology_key(topology=None) -> str:
    """Canonical mesh spelling for table keys: the >1-sized axes in a
    fixed order ("dp4xtp2"); a topology-less session keys on the visible
    device count ("dp8"). DCN-tagged axes carry their link class in the
    spelling ("dp4dcnxfsdp2") so a hybrid 4×2 factorization can never
    share a table row with the flat all-ICI dp4xfsdp2 mesh — measured
    knob defaults are fabric-specific evidence."""
    if topology is None:
        try:
            import jax

            n = max(len(jax.devices()), 1)
        except Exception:  # noqa: BLE001
            n = 1
        return f"dp{n}"
    sizes = dict(getattr(topology, "sizes", None) or {})
    kinds = dict(getattr(topology, "link_kinds", None) or {})
    parts = [
        f"{a}{int(sizes[a])}" + ("dcn" if kinds.get(a) == "dcn" else "")
        for a in _AXIS_ORDER if int(sizes.get(a, 1)) > 1
    ]
    return "x".join(parts) or f"dp{int(getattr(topology, 'world_size', 1))}"


def model_class(mcfg) -> str:
    """Coarse model-class bucket for table keys: dense vs moe × analytic
    parameter-count bucket (s < 1e9 <= m < 1e10 <= l)."""
    if mcfg is None:
        return "unknown"
    moe = bool(getattr(mcfg, "is_moe", False))
    n = 0.0
    try:
        n = float(mcfg.num_params())
    except Exception:  # noqa: BLE001 — a config without the protocol
        pass
    bucket = "s" if n < 1e9 else ("m" if n < 1e10 else "l")
    return ("moe-" if moe else "dense-") + bucket


def load_knob_table(path: Optional[str] = None) -> Dict[str, Any]:
    """The default-knob table: explicit ``path``, else the
    ``DSTPU_KNOB_TABLE`` env override, else the packaged data file.
    Unreadable/corrupt tables are an EMPTY table, never a crash — the
    conservative off defaults then resolve everywhere."""
    p = path or os.environ.get(KNOB_TABLE_ENV) or _PACKAGED_KNOB_TABLE
    try:
        with open(p) as f:
            table = json.load(f)
    except (OSError, ValueError):
        return {"version": 1, "entries": []}
    if not isinstance(table, dict) or not isinstance(
        table.get("entries"), list
    ):
        return {"version": 1, "entries": []}
    return table


def lookup_knob_row(table: Dict[str, Any], gen: str, topo_key: str,
                    mclass: str) -> Tuple[Optional[Dict[str, Any]], str]:
    """(row, provenance) for one (gen, topology, model_class) key. Exact
    gen first, then the GEN_FALLBACKS chain (v6e missing → the v5e row),
    topology and model class always exact — a measured dp4xtp2 row says
    nothing about dp8. provenance names where the row came from
    ("table:v5e/dp4xtp2/dense-s"); a miss is (None, "miss")."""
    entries = table.get("entries") or []

    def find(g: str) -> Optional[Dict[str, Any]]:
        for row in entries:
            if (row.get("gen") == g and row.get("topology") == topo_key
                    and row.get("model_class") == mclass):
                return row
        return None

    for g in (gen, *GEN_FALLBACKS.get(gen, ())):
        row = find(g)
        if row is not None:
            return row, f"table:{g}/{topo_key}/{mclass}"
    return None, "miss"


@dataclass
class HardwareModel:
    """Per-device capability numbers the roofline and budget checks use."""

    gen: str = "v5e"
    peak_flops: float = 197e12        # bf16 MXU peak, flops/s
    hbm_bytes: float = 16 * _GIB      # HBM capacity (the default R6 budget)
    hbm_bw: float = 819e9             # HBM bandwidth, bytes/s
    ici_bw: float = 45e9              # per-link ICI bandwidth, bytes/s
    host_bw: float = 32e9             # host DMA link, bytes/s
    dcn_bw: float = 3.125e9           # per-device inter-pod DCN, bytes/s

    @classmethod
    def detect(cls) -> "HardwareModel":
        """Defaults for the local generation + the env overrides.

        ``DSTPU_TPU_GEN`` pins the generation; otherwise a live CPU
        backend selects the ``cpu`` envelope (so lint-mesh plans and drift
        checks price the machine that actually runs them) and a live TPU
        backend reads the real chip generation off
        ``jax.devices()[0].device_kind`` — a kind or generation that is
        not in the table raises."""
        gen = detect_gen()
        d = gen_defaults(gen)
        hbm = d["hbm_bytes"]
        hbm_gb = os.environ.get("SHARDPLAN_HBM_GB")
        if hbm_gb:
            hbm = float(hbm_gb) * _GIB
        ici_env = os.environ.get("BENCH_ICI_BW_GBS")
        host_env = os.environ.get("BENCH_HOST_BW_GBS")
        dcn_env = os.environ.get("BENCH_DCN_BW_GBS")
        return cls(
            gen=gen,
            peak_flops=d["peak_flops"],
            hbm_bytes=hbm,
            hbm_bw=d["hbm_bw"],
            ici_bw=float(ici_env) * 1e9 if ici_env else d["ici_bw"],
            host_bw=float(host_env) * 1e9 if host_env else d["host_bw"],
            dcn_bw=float(dcn_env) * 1e9 if dcn_env else d["dcn_bw"],
        )
