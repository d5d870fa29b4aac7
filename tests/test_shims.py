"""Sunset tests for the project's retired compatibility shims.

The four PR-6 paged-protocol shims (``lm.prefill_paged``,
``lm.decode_step_paged``, ``lm.prefill_chunk_paged``,
``encdec.decode_step_paged``) hit their ``PAGED_SHIMS_SUNSET`` of 0.2
and were deleted at version 0.2.0; ``test_paged_shims_stay_retired``
pins that they do not creep back.
"""

from __future__ import annotations

from repro.models import encdec, lm

# --------------------------------------------------------------------------
# paged-protocol shims (PR 6): retired at their 0.2 sunset
# --------------------------------------------------------------------------


def test_paged_shims_stay_retired():
    """The deprecated paged entry points were deleted at version 0.2.0
    (their ``PAGED_SHIMS_SUNSET``); callers drive ``lm.paged_prefill`` /
    ``lm.paged_decode`` / ``encdec.paged_decode`` or the
    ``repro.models.api`` paged protocol.  Nothing may reintroduce the
    old names or the sunset constant."""
    for mod, name in ((lm, "prefill_paged"), (lm, "decode_step_paged"),
                      (lm, "prefill_chunk_paged"),
                      (lm, "PAGED_SHIMS_SUNSET"),
                      (encdec, "decode_step_paged")):
        assert not hasattr(mod, name), (
            f"{mod.__name__}.{name} reappeared after its 0.2 sunset")
    # the modern entry points the shims delegated to must still exist
    for mod, name in ((lm, "paged_prefill"), (lm, "paged_decode"),
                      (encdec, "paged_decode")):
        assert callable(getattr(mod, name))
