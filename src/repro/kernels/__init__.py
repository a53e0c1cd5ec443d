"""Pallas kernels for the compute hot-spots DiLoCoX actually optimizes.

Modules
-------
- ``lowrank_mm.py`` / ``quant4.py`` / ``flash_attention.py``: single-op
  kernels (PowerSGD projections, block-256 symmetric int4 pack/unpack,
  flash attention forward) with eager references in ``ref.py``.
- ``fused_compress.py``: the fused Alg. 1+2 outer-step pipeline —
  the rank-r PowerSGD projections of the EF-corrected delta (δ + e) with
  the EF add done on the operand tile in VMEM, block-wise int4
  quantize+pack of both factors, and the *new* EF residual
  e' = (δ + e) − decompress fused with the reconstruction, plus the
  decompress dual for the receive side.  The unfused oracle chain is
  ``ref.outer_step_ref``; ``ops.fused_outer_step`` dispatches between them
  on ``REPRO_USE_PALLAS=1``.

Adaptive-rank contract (jit shape stability)
--------------------------------------------
All rank-r entry points accept a traced ``rank_scalar`` r_t ≤ r_max and
keep every output at the static r_max shape, with columns ≥ r_t masked
to exactly zero (factors, warm-start Q, packed payload codes).  One
compiled executable therefore serves the whole Alg. 3 rank schedule.

Interpret mode vs the TPU
-------------------------
Every ``pallas_call`` here takes its ``interpret`` flag from
``pallas_interpret()``, read when the kernel is traced: the Pallas
interpreter on the CPU backend (the correctness lane the tests run), the
Mosaic-compiled kernel on TPU, and an error on any other backend — the
kernels use TPU memory spaces, so there is no third lane to fall back to.
Block shapes follow the TPU's rules in both modes (second-minor dims a
multiple of 8/16, minor dims a multiple of 128 or the whole axis, 2-D
scale blocks) and tiles are sized to the 16 MiB scoped-VMEM default, so
what passes the interpret-mode gates in ``tests/test_kernels.py`` also
compiles for the chip (``tests/test_tpu_compile.py``).
"""
import jax

# the kernels' in-kernel matmul precision: f32 MXU passes, the semantics
# of the f32 references they are tested against
F32 = jax.lax.Precision.HIGHEST


def pallas_interpret() -> bool:
    """Whether ``pallas_call`` runs in interpret mode, decided from the
    default backend: True on CPU, False on TPU, and a ``RuntimeError``
    anywhere else."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"the Pallas kernels target TPU (compiled) or CPU "
                       f"(interpreted); backend {platform!r} has neither")
