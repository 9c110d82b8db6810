"""What every cell shares: the cell's files, the device, the compile
cache and clock, the program's model built from a configuration file,
and the result line.

A cell (``BENCHMARK.json`` ``workloads`` entry) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  The mix's ``kind`` picks the driver
(``bench/drivers/<kind>.py``); its limits for ``correct`` are in
``bench/limits/<workload>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding any of them adds files only.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
CACHE_DIR = BENCH / ".cache" / "jax"
OUT_DIR = BENCH / ".out"


class NoResult(SystemExit):
    """Ends the run with a nonzero code and no result line."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise NoResult(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")

    def mine(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if mine(m) and m["moves"] in names]
    return Cell(workload, int(w["chips"]), config, traffic,
                load_json(root / "bench" / "limits" / f"{workload}.json"),
                e2e, per_layer)


def keep_logs_inside() -> None:
    """libtpu logs to ``/tmp/tpu_logs`` unless told otherwise: keep them
    in the checkout, before JAX is imported."""
    if "TPU_LOG_DIR" not in os.environ:
        (OUT_DIR / "tpu_logs").mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(OUT_DIR / "tpu_logs")


def program_on_path(root: Path = ROOT) -> None:
    src = root / "src"
    if not (src / "repro").is_dir():
        raise NoResult(f"no program under {src}: run from a checkout "
                       "of the repository")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def use_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(chips: int):
    """The accelerator devices, or no result."""
    import jax
    backend = jax.default_backend()
    if backend not in ("tpu", "gpu"):
        raise NoResult(f"JAX found no accelerator (backend {backend!r})")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoResult(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    return devices


class CompileClock:
    """Counts and times JAX's backend compiles (persistent-cache loads
    included), so the window can show that it compiled nothing."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs


def lm_config(cfg: dict):
    """The program's ``LMConfig`` for a configuration file, refusing a
    file whose numbers the program's block cannot run as stated."""
    from repro.models.transformer import LMConfig

    from bench.reference.decoder import knob
    for key in ("embedding_multiplier", "residual_multiplier",
                "logits_scaling", "partial_rotary_factor",
                "attention_multiplier"):
        want = cfg["head_dim"] ** -0.5 if key == "attention_multiplier" \
            else 1.0
        if abs(knob(cfg, key) - want) > 1e-12:
            raise NoResult(f"{cfg['name']}: the program runs {key}="
                           f"{want}, the file says {knob(cfg, key)}")
    if cfg["rms_norm_eps"] != 1e-6:
        raise NoResult("the program's RMSNorm epsilon is 1e-6")
    if cfg.get("rope_scaling") is not None or cfg["hidden_act"] != "silu":
        raise NoResult("the program runs plain RoPE and SwiGLU only")
    return LMConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"], mlp_kind="swiglu", norm_kind="rmsnorm",
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["torch_dtype"])


class SeededModel:
    """The program's model with the benchmark's weights: ``init`` returns
    the seeded weights of :mod:`bench.reference.weights`; everything else
    is the program's own."""

    def __init__(self, inner, init_fn):
        self._inner = inner
        self._init_fn = init_fn

    def init(self, key=None):
        return self._init_fn()

    def __getattr__(self, name):
        return getattr(self._inner, name)


def program_model(cfg: dict, seed: int):
    """(program model with seeded weights, weight maker)."""
    import jax
    from repro.models.transformer import DecoderLM

    from bench.reference import weights

    inner = DecoderLM(lm_config(cfg))

    def make():
        return weights.make(cfg, weights.seed_key(seed, 0))

    want = jax.eval_shape(inner.init, jax.random.PRNGKey(0))
    have = jax.eval_shape(make)
    same = jax.tree.structure(want) == jax.tree.structure(have) and all(
        (a.shape, a.dtype) == (b.shape, b.dtype) for a, b in zip(
            jax.tree.leaves(want), jax.tree.leaves(have), strict=True))
    if not same:
        raise NoResult("the program's parameter tree differs from the "
                       "benchmark's weight layout")
    return SeededModel(inner, make), make


def peak_memory(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


@dataclass
class Outcome:
    """What a driver hands back to :func:`bench.run.main`."""

    attempted: int
    failed: int
    e2e: dict[str, float]
    record: dict[str, Any]                 # read by bench/metrics/*.py
    compared: dict[str, tuple[float, float]]
    memory_peak_bytes: int
    trace: Any = None                      # bench.trace summary or None
    notes: dict[str, Any] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(
            v == v and v <= lim for v, lim in self.compared.values())
