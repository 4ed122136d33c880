"""Pallas TPU kernels for the SMO iteration hot spots.

The paper's per-iteration cost is dominated by kernel-row evaluation and the
O(l) selection/update vector work (§2: steps 1, 3, 4 are O(l)).  On TPU the
iteration is restructured into exactly TWO fused passes over the sharded
example dimension (DESIGN.md §3):

* pass A (``rbf_row_wss``):   compute the kernel row k_i from X, and in the
  same VMEM-resident pass evaluate the WSS2 second-order gains (eq. 3, or
  the exact clipped gain for Alg. 3's guard branch) and their running
  per-lane argmax.  Output: the selection (j, gain); k_i stays in VMEM.
* pass B (``rbf_update_wss``): recompute k_i and compute k_j (neither
  materialized to HBM), apply the gradient update
  G <- G - mu (k_i - k_j), and in the same pass compute the next
  iteration's first-order argmax over I_up and both KKT gap endpoints.

Everything O(1) in between (Newton step, planning-ahead eq. 8, box logic)
happens on scalars outside the kernels.  ``gram_block`` provides the tiled
Gram-matrix builder used by batch mode and the SVM-probe feature path.
"""
