"""Compiles for a described TPU v5e, with no chip attached: the Pallas
kernels at real widths, and one deepseek-7b cais layer on a
``data=1 x model=4`` mesh of a v5e 2x2 host, forward, and forward with
its gradient on the flash attention kernel and on the XLA core.

The TPU compiler ships with jax, so these catch what interpret mode cannot
(block tiling, VMEM, partitioning, collective choice) at no chip time.
Nothing runs: they say nothing about results or times. The topology is
described inside a fixture, never at import, because only one process at
a time may load the TPU library; keep every such compile in this file."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.configs import get_arch
from repro.core import tp
from repro.kernels import ops
from repro.launch import specs as S
from repro.models import build_model
from repro.roofline.hlo_costs import collective_counts


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host, compiled for with the persistent
    compilation cache off (its entries could not be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 (any failure: no compiler)
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _bf16(shape, sh):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sh)


KERNELS = {
    "matmul_4096x4096x11008": (ops.matmul,
                               [(4096, 4096), (4096, 11008)]),
    "matmul_rmsnorm_4096x11008x4096": (ops.matmul_rmsnorm,
                                       [(4096, 11008), (11008, 4096),
                                        (4096,)]),
    "flash_attention_d128_s4096": (functools.partial(ops.flash_attention,
                                                     causal=True),
                                   [(8, 4096, 128)] * 3),
    "flash_attention_d256_s4096": (functools.partial(ops.flash_attention,
                                                     causal=True),
                                   [(4, 4096, 256)] * 3),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_natively_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    compiled = jax.jit(fn).lower(*[_bf16(s, one_chip)
                                   for s in shapes]).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_matmul_unaligned_rows_raise_clear_error(one_chip):
    """4100 rows have no (8, 128)-aligned block divisor: the kernel says so
    with the shape, before Mosaic's lowering error."""
    with pytest.raises(ValueError, match=r"dim 4100 of shape \(4100, 4096\)"):
        ops.matmul.lower(_bf16((4100, 4096), one_chip),
                         _bf16((4096, 11008), one_chip))


def _deepseek_layer(topo, batch, seq):
    """``(model, mesh, params, tokens)``: one deepseek-7b layer at published
    widths for the cais backend on a described ``data=1 x model=4`` mesh,
    as shapes."""
    cfg = get_arch("deepseek-7b").scaled(num_layers=1)
    rt = S.runtime_for(cfg, tp_mode="cais")
    model = build_model(cfg, rt)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4),
                (sharding.DATA_AXIS, sharding.MODEL_AXIS),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        shapes, S.param_shardings(cfg, mesh, shapes))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                  sharding=NamedSharding(mesh, P()))
    return model, mesh, params, tokens


def test_deepseek_cais_layer_forward_compiles_with_permutes(topo):
    """One deepseek-7b layer at published widths, TP over a described
    four-chip ring with the cais backend: the collectives lower to
    collective-permute chains."""
    model, mesh, params, tokens = _deepseek_layer(topo, 1, 2048)
    with sharding.use_mesh(mesh):
        compiled = jax.jit(lambda p, t: model.forward(p, t)[0]).lower(
            params, tokens).compile()
    census = collective_counts(compiled.as_text())
    assert census["collective-permute"] > 0, census


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _kernel_scopes(text):
    """The ``op_name`` of every ``tpu_custom_call`` in a compiled text (a
    kernel's metadata can follow a multi-line ``kernel_metadata`` block)."""
    return [_OP_NAME.search(text, m.end()).group(1)
            for m in re.finditer(r'custom_call_target="tpu_custom_call"',
                                 text)]


def test_deepseek_cais_layer_grad_runs_flash_kernels(topo, monkeypatch):
    """The layer's forward and gradient at the ring cell's batch (4 x 4096
    tokens): with the kernel engaged, the attention core's forward and
    fused backward kernels are ``tpu_custom_call``s under ``attn.flash``,
    and the step needs fewer temporary bytes than with the XLA core. The
    test engages the kernel itself: the default backend here is the CPU."""
    model, mesh, params, tokens = _deepseek_layer(topo, 4, 4096)

    def compiled(flash: bool):
        monkeypatch.setattr(tp, "_on_tpu", lambda: flash)
        jax.clear_caches()  # the core is chosen at trace time
        loss = lambda p, t: model.loss(p, {"tokens": t, "labels": t})  # noqa: E731
        with sharding.use_mesh(mesh):
            return jax.jit(jax.grad(loss)).lower(params, tokens).compile()

    flash = compiled(True)
    kernels = _kernel_scopes(flash.as_text())
    assert kernels and all("attn.flash" in k for k in kernels), kernels
    assert any("/attn.flash/" in k and "splash_mha_fwd" in k
               and "transpose(" not in k for k in kernels), kernels
    assert any("transpose(jvp(attn.flash))" in k and "splash_mha_dkv" in k
               for k in kernels), kernels
    xla = compiled(False)
    assert "tpu_custom_call" not in xla.as_text()
    assert (flash.memory_analysis().temp_size_in_bytes
            < xla.memory_analysis().temp_size_in_bytes)
