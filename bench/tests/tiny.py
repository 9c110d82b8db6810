"""Cells at a size a CPU test run holds: the cells' own drivers, traffic
kinds and checks, with the configuration's widths and the traffic's
lengths shrunk."""

from __future__ import annotations

import copy

from bench import harness

GRANITE_TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256,
    # the full width's gain per matmul (0.02 * sqrt(2048) ~ 1): at width
    # 64, N(0, 0.02) weights would leave attention a small share of the
    # residual stream, and a fault in it unseen
    "initializer_range": 0.15,
}

# Limits at this size, set as on the chip from bench/calibrate.py's
# readings on the CPU, seeds 11, 22, 33, 44, 4242424242, 12345678901
# (program max / control min): m 1.6e-3 / 9.3e-3,
# v 5.7e-3 / 2.7e-2, update 2.0e-3 / 3.7e-3 (too close: its upper is the
# unchanged state's 1).
TINY_LIMITS = {
    "granite-train-1chip": {"m_gap": 4e-3,
                            "v_gap": 1.2e-2, "update_gap": 0.02},
}

# (configuration, traffic mix) of each cell the tests shrink
CELLS = {"granite-train-1chip": ("granite-3-2b", "train-seq4096-h5")}


def config(name: str) -> dict:
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


def cell(workload: str, chips: int = 1, **traffic_changes) -> harness.Cell:
    """The named cell with tiny widths and short rows."""
    cfg_name, mix_name = CELLS[workload]
    cfg = copy.deepcopy(config(cfg_name))
    mix = harness.load_json(harness.BENCH / "traffic" / f"{mix_name}.json")
    cfg.update(GRANITE_TINY)
    mix.update(seq=64)
    mix.update(traffic_changes)
    limits = {k: {"limit": v} for k, v in TINY_LIMITS[workload].items()}
    return harness.Cell(workload, chips, cfg, mix, limits, [], [])


class Ctx:
    """A run context without the chip: no profiler, memory unread."""

    def __init__(self, cell, seed=12345678901, seconds=1.0):
        self.cell, self.seed, self.seconds, self.trace = (cell, seed,
                                                          seconds, False)

    def limit(self, name):
        return self.cell.limits[name]["limit"]

    def window_opens(self):
        pass

    def window_closes(self):
        return 0

    def peak_memory(self):
        return 0


def run(cell, **kw):
    harness.program_on_path()
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / f"{cell.kind}.py",
                                 f"bench_driver_{cell.kind}")
    return driver.run(Ctx(cell, **kw))
