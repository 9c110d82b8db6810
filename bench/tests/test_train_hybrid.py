"""The hybrid training driver on a tiny cell on the CPU: a sound run is
correct, a broken one is not, the control fails the limits; and the
readers of ``ssd_ms.train`` and ``mfu_hybrid.train``."""

import importlib.util

import pytest

from bench import harness
from bench import trace as tr
from bench.tests import tiny

harness.program_on_path()

import repro.models.mamba2 as mamba2  # noqa: E402

WORKLOAD = "granite-h-train-1chip"
HYBRID_TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_chunk_size": 16,
    "attention_multiplier": 1 / 16,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    # as tiny.GRANITE_TINY: the full width's gain per matmul at width 64
    "initializer_range": 0.15,
}
# Limits at this size from the driver's readings on the CPU, seeds 11,
# 22, 33, 4242424242, 12345678901 (program max / control min / half a
# batch min): m 5.3e-3 / 5.7e-3 / 0.41 (the control too close: limit
# from half a batch), v 5.0e-3 / 9.7e-3 / 1.45, update 1.5e-3 / 3.8e-3
# / 0.024.
TINY_LIMITS = {"m_gap": 1e-2, "v_gap": 7e-3, "update_gap": 2.5e-3}


def tiny_cell() -> harness.Cell:
    cfg = tiny.config("granite-4.0-h-micro")
    cfg.update(HYBRID_TINY)
    mix = harness.load_json(harness.BENCH / "traffic"
                            / "train-hybrid-seq4096-h5.json")
    mix.update(seq=64)
    limits = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    return harness.Cell(WORKLOAD, 1, cfg, mix, limits, [], [])


def test_sound_run_is_correct():
    out = tiny.run(tiny_cell())
    assert out.correct, out.compared
    assert out.record["compiles_in_window"] == 0


def test_conv_left_out_is_not_correct(monkeypatch):
    monkeypatch.setattr(mamba2, "_causal_conv",
                        lambda w, bias, u: mamba2.jax.nn.silu(u + bias))
    out = tiny.run(tiny_cell())
    assert not out.correct, out.compared


def test_control_and_half_batch_fail_a_limit():
    from bench.drivers import train_hybrid
    cell = tiny_cell()
    got = train_hybrid.readings(cell, 4242424242)
    assert all(v <= cell.limits[k]["limit"]
               for k, v in got["program"].items()), got
    for bad in ("control", "half_batch"):
        assert any(v > cell.limits[k]["limit"]
                   for k, v in got[bad].items()), (bad, got)


def _reader(name):
    path = harness.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


DEV0 = "/device:TPU:0"
SSD = "jit(phase_0)/vmap(jvp(fwd))/while/body/mamba_mixer/ssd/dot_general"
MIXER = "jit(phase_0)/vmap(jvp(fwd))/while/body/mamba_mixer/dot_general"
BWD_SSD = "jit(phase_0)/vmap(transpose(jvp(fwd)))/while/body/" \
    "mamba_mixer/ssd/mul"
HLO = f"""HloModule jit_phase_0, entry_computation_layout={{()->()}}

ENTRY %main {{
  %fusion.1 = f32[8]{{0}} fusion(%a), metadata={{op_name="{SSD}"}}
  %fusion.2 = bf16[8]{{0}} fusion(%a), metadata={{op_name="{MIXER}"}}
  %fusion.3 = f32[8]{{0}} fusion(%a), metadata={{op_name="{BWD_SSD}"}}
  ROOT %copy.4 = bf16[8]{{0}} copy(%c)
}}
"""


def test_scope_ms_on_a_synthesised_trace():
    """Two steps in the traced period: 3 ms of SSD ops (forward and
    backward), 1 ms more of the mixer, 2 ms outside it."""
    from bench.drivers import train_hybrid

    def ev(name, s, e, line=tr.OPS_LINE, plane=DEV0):
        return tr.Event(plane, line, name, s * 1e6, e * 1e6)

    events = [
        ev("jit_phase_0(123)", 0, 10, line="XLA Modules"),
        ev("%fusion.1 = f32[8]{0} fusion(%a)", 1, 3),
        ev("%fusion.2 = bf16[8]{0} fusion(%a)", 3, 4),
        ev("%fusion.3 = f32[8]{0} fusion(%a)", 4, 5),
        ev("%copy.4 = bf16[8]{0} copy(%c)", 6, 8),
        tr.Event("/host:CPU", "python", "bench.period", 0.0, 10e6),
    ]
    got = train_hybrid.scope_ms(events, {"jit_phase_0": HLO}, steps=2)
    assert got["ssd"] == pytest.approx(1.5)
    assert got["mamba_mixer"] == pytest.approx(2.0)
    assert got["fwd"] == pytest.approx(1.5)
    assert got["bwd"] == pytest.approx(0.5)
    assert got["other"] == pytest.approx(1.0)
    read = _reader("ssd_ms.train")
    assert read({"record": {"scope_ms": got}}) == pytest.approx(1.5)
    assert read({"record": {}}) is None


def test_mfu_hybrid_reader():
    """One 4096-token period a second on a hybrid file reads its FLOPs
    a token over the peak; a file without layer types reads nothing."""
    from bench.flops_hybrid import train_flops_per_token
    from bench.peaks import PEAKS
    cfg = tiny.config("granite-4.0-h-micro")
    rec = {"record": {"period": 1, "workers": 1, "batch_per_worker": 1,
                      "seq": 4096, "chips": 1},
           "window": (0, 1e9), "config": cfg,
           "peaks": PEAKS["TPU v5 lite"]}
    want = 100 * 4096 * train_flops_per_token(cfg, 4096) / 197e12
    assert _reader("mfu_hybrid.train")(rec) == pytest.approx(want)
    assert _reader("mfu_hybrid.train")(
        {**rec, "config": tiny.config("granite-3-2b")}) is None
