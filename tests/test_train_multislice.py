"""Cross-slice (DCN) multi-process gate (VERDICT r4 #4): two separate
jax.distributed process groups of 2 devices each form a (dcn=2, ici=2)
mesh — the outer axis spans slices — and the workers assert the
hierarchical reduction: per-slice ICI psum partials [3, 7] then the
cross-slice DCN allreduce total 10 (a value only a real global mesh can
produce), plus a data-parallel train step whose gradient is reduced
ICI-first then DCN and matches the single-host computation.

Reference analog: multi-slice data parallelism over DCN
(jax.experimental.multihost_utils semantics; SURVEY §5 'Distributed
communication backend', §7 Phase 3 v5e-multi-slice shape).
"""

import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_two_slice_hierarchical_psum_and_grad_step():
    sys.path.insert(0, _REPO_ROOT)
    import __graft_entry__ as ge

    outs = ge._spawn_entry_workers("--two-slice-worker", 2)
    for rank, out in enumerate(outs):
        assert f"two-slice-worker rank={rank}" in out and "ok" in out, out
        # the per-slice ICI partials and the DCN total are printed by each
        # worker; check the asserted values made it through
        assert "partials=[3.0, 7.0]" in out, out
        assert "total=10.0" in out, out
