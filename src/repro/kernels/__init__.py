"""Pallas TPU kernels for the paper's three compute hot-spots.

The paper's retrieval loop spends its time in exactly three places —
envelope construction, the LB_Keogh pass, and the banded DTW DP — and
optimizes each (Algorithm 1, Algorithm 2/3, the O(nw) DP).  Each gets a
TPU kernel here, with the layout rethought for VMEM/VPU execution
(DESIGN.md §3):

* ``envelope``    — van Herk–Gil–Werman sliding min/max (replaces the
  sequential deque of the paper's Algorithm 1).
* ``lb_kim``      — constant-time first/last/extremum bound (Kim); runs
  before the envelope stages, needs no envelopes, four scalars per lane.
* ``lb_keogh``    — fused clamp-project-accumulate; emits the powered bound
  AND the projection H(c, q) in one VMEM pass (feeds LB_Improved pass 2).
* ``lb_improved`` — fused pass 2: envelope of the projection + second
  accumulation in one VMEM pass (the two-pass contribution itself).
* ``lb_fused``    — both passes in ONE launch (DESIGN.md §3.6): the
  candidate tile stays resident in VMEM, pass 2 is predicated per lane
  on the powered pruning bound, and the projection never touches HBM —
  one HBM read of the block instead of up to three.
* ``dtw``         — banded DP with the loop-carried band row resident in
  VMEM; within-row recurrence solved by cumsum+cummin doubling.  The
  row loop is a ``while_loop`` threaded with a per-lane powered bound
  (early abandoning, paper §3): rows stop once the band's running min
  clears the bound — the device twin of ``core.dtw.dtw_banded_early``.

The LB kernels also come in query-major ``*_qbatch_op`` variants
(DESIGN.md §3.4): the query batch is a second grid dimension, so one
launch computes bounds for every (query, candidate) pair of a block —
the kernel-level mirror of the batched cascade.  The stream-packed
``*_stream_qbatch_op`` variants (DESIGN.md §3.5) take a flat stream
segment instead of a candidate matrix and slice hop-strided window
lanes out of it in VMEM, so the overlapping windows of a subsequence
sweep are never materialized in HBM.

Kernels are validated in interpret mode against the pure-jnp oracles in
each ``ref.py`` (which are in turn validated against numpy DPs).
``TPU_READY`` names the families the TPU compiler accepts at deployment
widths (``tests/test_tpu_compile.py``); the others still need a
restructured kernel body before they can run on the chip.
"""

from repro.kernels.dtw import dtw_early_ref, dtw_op, dtw_ref
from repro.kernels.envelope import envelope_op, envelope_ref
from repro.kernels.lb_fused import lb_fused_qbatch_op, lb_fused_qbatch_ref
from repro.kernels.lb_improved import (
    lb_improved_op,
    lb_improved_pass2_op,
    lb_improved_pass2_qbatch_op,
    lb_improved_qbatch_op,
    lb_improved_qbatch_ref,
    lb_improved_ref,
    lb_improved_stream_qbatch_op,
    lb_improved_stream_qbatch_ref,
)
from repro.kernels.lb_kim import lb_kim_qbatch_op, lb_kim_qbatch_ref
from repro.kernels.lb_keogh import (
    lb_keogh_op,
    lb_keogh_qbatch_op,
    lb_keogh_qbatch_ref,
    lb_keogh_ref,
    lb_keogh_stream_qbatch_op,
    lb_keogh_stream_qbatch_ref,
    materialize_windows,
)

#: families whose kernels compile for a TPU (``interpret=False``) at
#: the paper's n=1000, w=100; tests/test_tpu_compile.py pins the others
#: as expected compiler refusals
TPU_READY = ("lb_keogh", "lb_kim")

__all__ = [
    "TPU_READY",
    "dtw_early_ref",
    "dtw_op",
    "dtw_ref",
    "envelope_op",
    "envelope_ref",
    "lb_fused_qbatch_op",
    "lb_fused_qbatch_ref",
    "lb_improved_op",
    "lb_improved_pass2_op",
    "lb_improved_pass2_qbatch_op",
    "lb_improved_qbatch_op",
    "lb_improved_ref",
    "lb_improved_qbatch_ref",
    "lb_improved_stream_qbatch_op",
    "lb_improved_stream_qbatch_ref",
    "lb_kim_qbatch_op",
    "lb_kim_qbatch_ref",
    "lb_keogh_op",
    "lb_keogh_qbatch_op",
    "lb_keogh_ref",
    "lb_keogh_qbatch_ref",
    "lb_keogh_stream_qbatch_op",
    "lb_keogh_stream_qbatch_ref",
    "materialize_windows",
]
