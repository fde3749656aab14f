"""Custom ops: places where measured XLA performance leaves headroom.

``backward.py`` — the hand-written backward passes (custom VJPs) for
the two hand-built forward kernels: the s2d stem conv's f32-accumulated
weight gradient and FusedBatchNorm's bf16-reads/f32-accumulation
backward, replacing XLA's materialize-as-f32 derivation on the train
step's gradient path (DESIGN.md §4; parity pinned by
tests/test_backward.py, registry closed by trace_lint check 9).

``attention.py`` — the token encoders' causal attention core as one
Pallas kernel (models/mla_moe.py ``fused_causal_core``, which the program
takes where it is lowered for the TPU): the einsum form it replaces wrote
the float32 ``[B, H, T, T]`` scores to HBM and went over them three times,
and computed the masked half (PERF.md section 6).

Only ops where measured XLA performance leaves headroom get a kernel.
The first kernel that lived here — ``kcenter_pallas``, the k-center
selection's fused batched distance-update + block-local argmax — failed
that bar on real hardware: the r5 on-MXU A/B measured 0.67x/1.11x/0.93x
the XLA scan with ``pallas_picks_match: False`` in all three runs, so it
was deleted per the r5 verdict rather than kept as an env-var-gated trap.
The full decision record (what the kernel fused, why XLA's matvec was
already HBM-bound, and the bar any future kernel must clear) is DESIGN.md
§5.
"""
