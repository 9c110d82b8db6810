"""Compile the main path for a described TPU v5e — no chip attached.

The TPU compiler is installed with JAX and compiles for a topology it is
given as a description.  It refuses what the chip would refuse (Mosaic
block shapes that break the (8, 128) tiling rule, programs that do not fit
HBM), which interpret mode and the CPU backend never see.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so under pytest-xdist the
worker that runs this file loads it and every other worker still collects
the same tests.  The persistent compilation cache is off around these
compiles (an entry written for a described chip cannot be read back).
"""

import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.api import JobConfig, Session
from repro.configs import get_arch
from repro.configs.granite_3_2b import CONFIG as GRANITE
from repro.configs.granite_4_0_h_micro import CONFIG as GRANITE_H
from repro.kernels.paged_attention.kernel import paged_attention_fwd
from repro.models.transformer import DecoderLM
from repro.optim import make_optimizer
from repro.runtime import init_train_state, make_train_step

HBM_BYTES = 16e9            # one v5e chip: 16 GB of HBM


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                          # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on_chip(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("skip_pages", [True, False])
@pytest.mark.parametrize("arch", ["granite-3-2b", "qwen3-1.7b"])
def test_paged_attention_compiles_for_v5e(one_chip, arch, skip_pages):
    """The serve engine's decode kernel at the config's published GQA
    widths: 8 slots, 16-token pages, 1088-token lanes, bf16 pages."""
    args = _paged_args(one_chip, arch)
    fwd = jax.jit(lambda *a: paged_attention_fwd(*a, skip_pages=skip_pages))
    compiled = fwd.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_kernel_carries_its_name(one_chip):
    """The kernel's name reaches the program the chip runs, where a
    device trace can find it."""
    lowered = jax.jit(paged_attention_fwd).lower(
        *_paged_args(one_chip, "granite-3-2b"))
    assert "repro_paged_attention" in lowered.as_text()


def _paged_args(one_chip, arch):
    """Decode-kernel inputs at the config's published GQA widths: 8
    slots, 16-token pages, 1088-token lanes, bf16 pages."""
    cfg = get_arch(arch).make_model().cfg
    slots, page, max_blocks = 8, 16, 68
    n_pages = 1 + slots * max_blocks
    pages = _on_chip(one_chip, (n_pages, page, cfg.n_kv_heads, cfg.hd),
                     jnp.bfloat16)
    return (_on_chip(one_chip, (slots, cfg.n_heads, cfg.hd), jnp.bfloat16),
            pages, pages,
            _on_chip(one_chip, (slots, max_blocks), jnp.int32),
            _on_chip(one_chip, (slots,), jnp.int32))


def _granite_step(workers, seq, place):
    """A granite-3-2b DreamDDP phase step at published widths, one layer,
    one ``seq``-token row per worker, and its state and batch as shapes
    that ``place`` gives a sharding."""
    model = DecoderLM(replace(GRANITE, n_layers=1))
    sess = Session(JobConfig(arch="granite-3-2b", smoke=False,
                             workers=workers, period=2, seq=seq,
                             batch_per_worker=1), model=model)
    plan, scfg = sess.plan, sess.step_config
    opt = make_optimizer("adam")
    state = jax.eval_shape(lambda: init_train_state(
        model, opt, jax.random.PRNGKey(0), workers, cfg=scfg))
    state = jax.tree.map(lambda s: place(s.shape, s.dtype), state)
    batch = {k: place((workers, 1, seq), jnp.int32)
             for k in ("tokens", "labels")}
    phase = next(h for h in range(plan.H) if plan.units_for_phase(h))
    step = jax.jit(make_train_step(model, opt, plan, phase, cfg=scfg),
                   donate_argnums=0)
    return step, state, batch


def test_hybrid_phase_step_fits_v5e(one_chip):
    """granite-4.0-h-micro's phase step at published widths, one Mamba-2
    layer before one attention layer, W=1, seq 4096, compiles for the
    chip within its HBM: attention on the flash kernels at the scale
    1/64, the mixer's ops under ``mamba_mixer`` and the chunked SSD's
    under ``ssd``, forward, remat and backward."""
    model = DecoderLM(replace(GRANITE_H, n_layers=2,
                              layer_types=("mamba", "attention")))
    sess = Session(JobConfig(arch=GRANITE_H.name, smoke=False, workers=1,
                             period=2, seq=4096, batch_per_worker=1),
                   model=model)
    plan, scfg = sess.plan, sess.step_config
    opt = make_optimizer("adam")
    state = jax.eval_shape(lambda: init_train_state(
        model, opt, jax.random.PRNGKey(0), 1, cfg=scfg))
    place = lambda s: _on_chip(one_chip, s.shape, s.dtype)  # noqa: E731
    state = jax.tree.map(place, state)
    batch = {k: _on_chip(one_chip, (1, 1, 4096), jnp.int32)
             for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(model, opt, plan, 0, cfg=scfg),
                   donate_argnums=0)
    compiled = step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    hlo = compiled.as_text()
    assert len(_flash_kernels(hlo)) == 3
    ssd = re.findall(r'op_name="[^"]*/mamba_mixer/ssd/[^"]*"', hlo)
    assert any("transpose(jvp(fwd))" in o and "rematted" not in o
               for o in ssd)
    assert any("rematted_computation" in o for o in ssd)
    assert any("transpose(" not in o for o in ssd)


def test_granite_phase_step_fits_v5e(one_chip):
    """One granite-3-2b DreamDDP phase step at published widths (one
    layer, W=2, one 1024-token sequence per worker) compiles for the
    chip, donating its state, within one chip's HBM."""
    step, state, batch = _granite_step(
        2, 1024, lambda shape, dtype: _on_chip(one_chip, shape, dtype))
    mem = step.lower(state, batch).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.alias_size_in_bytes > 0          # the state is donated
    assert total < HBM_BYTES, total


def _flash_kernels(hlo: str) -> list[str]:
    """Names of the flash-attention kernels the program runs: Mosaic
    custom calls under the ``repro_flash_attention`` scope.  An
    instruction's text may run over several lines."""
    return sorted(
        re.match(r"\s*%(splash_\w+?)(\.\d+)? =", instr).group(1)
        for instr in re.split(r"\n(?=\s+(?:ROOT )?%)", hlo)
        if 'custom_call_target="tpu_custom_call"' in instr
        and "repro_flash_attention" in instr)


def test_granite_phase_step_runs_flash_kernels(one_chip):
    """At the benchmark cell's widths and sequence (one layer, W=1, seq
    4096) the phase step's attention is the flash kernels: the forward,
    its recompute under the layer's remat, and the backward (dq and dkv
    in one kernel).  No float32 score map (``[..., 1024, 4096]``, one
    query chunk's) is left."""
    step, state, batch = _granite_step(
        1, 4096, lambda shape, dtype: _on_chip(one_chip, shape, dtype))
    hlo = step.lower(state, batch).compile().as_text()
    assert _flash_kernels(hlo) == [
        "splash_mqa_dkv_no_residuals", "splash_mqa_fwd_residuals",
        "splash_mqa_fwd_residuals"]
    assert not re.search(r"f32\[[\d,]*1024,4096\]", hlo)


def test_granite_phase_step_compiles_over_four_chips(topo, monkeypatch):
    """W=4 with the worker axis spread over a described 2x2 mesh: the
    step runs each chip's worker under ``shard_map`` (XLA cannot
    partition a Mosaic kernel), so it compiles with the kernels in it
    and no collective outside the phase's parameter sync."""
    monkeypatch.setattr(jax, "devices", lambda *_, **__: list(topo.devices))
    mesh = Mesh(np.asarray(topo.devices), ("data",))

    def place(shape, dtype):
        spec = P("data") if shape else P()
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    step, state, batch = _granite_step(4, 1024, place)
    hlo = step.lower(state, batch).compile().as_text()
    assert len(_flash_kernels(hlo)) == 3
    assert not re.search(r"all-gather|all-to-all|collective-permute", hlo)
