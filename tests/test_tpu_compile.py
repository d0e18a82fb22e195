"""Compile the TPU path's Pallas kernels for a described v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached.  The topology is built inside a module fixture
only (never at import), so every xdist worker collects the same tests and
only the worker running this file loads the TPU library.  The persistent
compilation cache is off around the compiles: entries written for a chip
that is not attached could not be read back.

Kernels that do not lower (``kernels.common.TPU_UNLOWERABLE``) are shown to
fail with the recorded message AND to be unreachable from every TPU policy.
"""
from __future__ import annotations

import os
import types

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import common as kc
from repro.kernels.aggregation.kernel import bin_rank_pallas
from repro.kernels.local_move import kernel as lm_kernel
from repro.kernels.local_move import ops as lm_ops

WIDTHS = (16, 64, 256)
# level-0 rows of com-youtube's widest-populated bucket (W=16: 678,043 rows
# at scale 1.0), and its table length n + 1
ROWS = 1 << 20
N_TABLE = 1 << 21


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


I32, F32, U32 = jnp.int32, jnp.float32, jnp.uint32


@pytest.fixture
def gathered_layout(monkeypatch):
    """The local_move dispatch's TPU layout decision, without a chip."""
    monkeypatch.setattr(lm_ops, "on_tpu", lambda: True)


@pytest.mark.parametrize("width", WIDTHS)
def test_plp_gathered_layout_compiles(one_chip, gathered_layout, width):
    """XLA gathers + ``label_argmax_pallas`` at the budgeted row block."""
    compiled = _compile(
        one_chip,
        lambda rows, nbr, w, lab, seed: lm_ops.local_move_plp(
            rows, nbr, w, lab, seed, tie_eps=0.25, sentinel=N_TABLE - 1,
            use_pallas=True, interpret=False),
        ((ROWS,), I32), ((ROWS, width), I32), ((ROWS, width), F32),
        ((N_TABLE,), I32), ((), U32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", WIDTHS)
def test_louvain_gathered_layout_compiles(one_chip, gathered_layout, width):
    """XLA gathers + ``delta_q_pallas`` at the budgeted row block."""
    compiled = _compile(
        one_chip,
        lambda rows, nbr, w, com, vol, size, deg: lm_ops.local_move_louvain(
            rows, nbr, w, com_ext=com, vol_ext=vol, size_ext=size,
            deg_ext=deg, vol_total=jnp.float32(1.0), sentinel=N_TABLE - 1,
            singleton_rule=True, use_pallas=True, interpret=False),
        ((ROWS,), I32), ((ROWS, width), I32), ((ROWS, width), F32),
        ((N_TABLE,), I32), ((N_TABLE,), F32), ((N_TABLE,), I32),
        ((N_TABLE,), F32))
    assert "tpu_custom_call" in compiled.as_text()


def _windows(win_blk):
    from repro.graph.ell import TableWindows

    return TableWindows(win_blk=win_blk, slot=1024, block_rows=256,
                        n_slots=kc.cdiv(4097, 1024))


# each TPU_UNLOWERABLE entry: (compile thunk args) at a small table
_UNLOWERABLE = {
    "local_move.resident": lambda: (
        lambda r, nb, w, lab, s: lm_kernel.local_move_plp_pallas(
            r, nb, w, lab, s, tie_eps=0.25, sentinel=4096, interpret=False),
        ((4096,), I32), ((4096, 16), I32), ((4096, 16), F32), ((4097,), I32),
        ((), U32)),
    "local_move.streamed": lambda: (
        lambda r, nb, w, lab, s, wb: lm_kernel.local_move_plp_pallas_streamed(
            r, nb, w, lab, s, tie_eps=0.25, sentinel=4096, interpret=False,
            windows=_windows(wb)),
        ((4096,), I32), ((4096, 16), I32), ((4096, 16), F32), ((4097,), I32),
        ((), U32), ((16,), I32)),
    "bin_rank": lambda: (
        lambda keys, cs, cd: bin_rank_pallas(keys, cs, cd, width=16,
                                             empty=4096, interpret=False),
        ((4097 * 16,), I32), ((4096,), I32), ((4096,), I32)),
}


@pytest.mark.parametrize("kernel", sorted(kc.TPU_UNLOWERABLE))
def test_unlowerable_kernel_fails_as_recorded(one_chip, kernel):
    """The record in TPU_UNLOWERABLE is the compiler's own message; when a
    compiler update makes one of these lower, this fails and the record
    (and the policies below) are due for an update."""
    fn, *shapes = _UNLOWERABLE[kernel]()
    msg = kc.TPU_UNLOWERABLE[kernel].split(": ", 1)[1]
    with pytest.raises(Exception) as ei:
        _compile(one_chip, fn, *shapes)
    assert msg in str(ei.value)


# ------------------------------------------- the TPU policies, steered here


@pytest.fixture
def as_tpu(monkeypatch):
    """Make the code's platform checks see a v5e, without a chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: [types.SimpleNamespace(device_kind="TPU v5 lite",
                                          platform="tpu")])
    monkeypatch.delenv(kc.VMEM_BUDGET_ENV, raising=False)


def test_bin_rank_kernel_never_chosen_on_tpu(as_tpu):
    assert kc.resolve_bin_impl("auto") == "ref"
    assert kc.resolve_bin_impl("ref") == "ref"
    with pytest.raises(ValueError, match="does not lower on TPU"):
        kc.resolve_bin_impl("kernel")


@pytest.mark.parametrize("windows", [None, "narrow"])
def test_local_move_pallas_is_gathered_on_tpu(as_tpu, windows):
    win = _windows(jnp.zeros((16,), jnp.int32)) if windows else None
    for n_tables in (1, 4):
        assert lm_ops._resolve_mode("auto", win, n_tables, 4096, None,
                                    use_pallas=True) == "gathered"
        for mode in ("resident", "streamed"):
            with pytest.raises(ValueError, match="does not lower on TPU"):
                lm_ops._resolve_mode(mode, win, n_tables, 4096, None,
                                     use_pallas=True)
    # the pure-jnp oracles keep their layouts: nothing there lowers to Mosaic
    assert lm_ops._resolve_mode("auto", None, 4, 4096, None) == "resident"


def test_interpret_mode_only_on_cpu(monkeypatch):
    assert kc.default_interpret() is True   # the suite runs on CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kc.default_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="cannot run"):
        kc.default_interpret()


def test_vmem_budget_known_kinds_only(as_tpu, monkeypatch):
    assert kc.vmem_budget_bytes() == kc.VMEM_BUDGET_BY_KIND["TPU v5 lite"]
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: [types.SimpleNamespace(device_kind="TPU v99",
                                          platform="tpu")])
    with pytest.raises(RuntimeError, match="no VMEM budget known"):
        kc.vmem_budget_bytes()
    monkeypatch.setenv(kc.VMEM_BUDGET_ENV, str(1 << 20))
    assert kc.vmem_budget_bytes() == 1 << 20
