import os
import sys

import pytest

# tests must see exactly ONE device (the dry-run sets its own flags in a
# subprocess); make `import repro` work regardless of invocation dir.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture
def gather_path(request, monkeypatch):
    """The gather path of the DR-SpMM arena kernels a test runs on
    (parametrize it indirectly): ``"vmem"``, the source slab resident as
    the kernels' own budget decides at these sizes; ``"dma"``, per-row
    DMAs, forced by a VMEM budget of 0; ``None``, no arena kernel at all.
    Checks afterwards that the test's launches took that path alone."""
    from _gather import launches
    from repro.kernels import drspmm as K
    path = request.param
    if path == "dma":
        monkeypatch.setattr(K, "_slab_budget", lambda other_bytes: 0)
    before = launches()
    yield path
    grew = {p for p, n in launches().items() if n > before[p]}
    assert grew == ({path} if path else set()), grew
