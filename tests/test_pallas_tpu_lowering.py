"""Every public Pallas entry point cross-lowers for the TPU — no chip.

``jax.jit(f).trace(*shapes).lower(lowering_platforms=("tpu",))`` runs
the Pallas TPU lowering (block-shape rules, unsupported primitives)
on the CPU, with ``interpret=False``, at the production geometries of
``npairloss_tpu.testing.pallas_cases``.  It is the cheap guard that
keeps a CPU-only change from re-breaking what Mosaic must accept; the
chip itself (``scripts/chip_kernel_check.py``) says whether Mosaic then
compiles the kernel and whether it computes the right numbers.
"""

import jax
import pytest

from npairloss_tpu.ops import pallas_mode
from npairloss_tpu.testing.pallas_cases import kernel_cases

CASES = kernel_cases()


def test_case_table_covers_every_entry_point():
    names = {c.name for c in CASES}
    for cfg in ("abs", "flagship", "flagship_radix", "flagship_nocache"):
        assert {f"blockwise_{cfg}_fwd", f"blockwise_{cfg}_grad"} <= names
    for tag in ("n480_c64_bfloat16", "n480_c192_bfloat16",
                "n1_c64_bfloat16", "n1_c192_bfloat16",
                "n120_c64_float32", "n8_c192_float32"):
        assert {f"lrn_fwd_{tag}", f"lrn_grad_{tag}"} <= names
    assert {"bias_relu", "bias_relu_pool", "probe_fp32", "probe_bf16",
            "probe_int8", "probe_fp32_shard_local"} <= names


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_lowers_to_a_mosaic_kernel(case):
    text = jax.jit(case.fn).trace(*case.specs).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def test_interpret_guard_forbids_silent_interpretation(monkeypatch):
    """Off-TPU the kernels interpret (the CPU suite's harness); under
    NPAIRLOSS_PALLAS_INTERPRET=forbid — what chip_smoke.py sets — a
    kernel that WOULD interpret raises instead."""
    assert pallas_mode.default_interpret() is True  # tests run on CPU
    monkeypatch.setenv(pallas_mode.INTERPRET_GUARD_ENV, "forbid")
    with pytest.raises(RuntimeError, match="interpreted"):
        pallas_mode.default_interpret()
