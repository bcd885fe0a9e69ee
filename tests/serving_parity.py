"""The serving tests' parity oracle: one reference, one written tolerance.

``SequentialReference`` threads each session one at a time through a jitted
one-row ``model.apply``. The engine answers the same requests from OTHER XLA
programs (batched prefill, warm tick, generic step, each with gather and
scatter around the model), so an engine answer is compared with the
reference by ``assert_same_answer``, never by ``np.array_equal``.

Exact equality (``np.array_equal`` / ``tobytes() ==``) stays in the serving
test files only where ONE program sees the SAME bytes: a carry that went
device_get -> host (RAM or disk) -> device_put and is stepped by the program
that would have stepped it anyway (warm unpark, spill adoption, checked
against an identically built engine that never parked it), an engine's
result after a JSON round trip over the wire (checked against that engine's
in-process reply), a migrated session's answer (checked against the
survivor's own answer to a session it has never seen), arena rows a tick did
not name, and bytes that are only moved (``_gather_rows`` against ``x[idx]``,
a spill record's payload: no arithmetic to round). Integer clocks and boolean
masks are compared exactly as a matter of course.
"""

from __future__ import annotations

import jax
import numpy as np

# Two XLA programs that compute the same float32 function (the engine's
# batched program and the one-row reference) may fuse, tile and order a
# reduction differently and so round the last digit differently. Observed on
# the CPU backend, in units of float32 epsilon x the largest |logit| of the
# pair: 2-3 units for the MLP and the episode transformer (6e-6 at |logit| 25,
# 1e-8 at |logit| 0.045), 6 units for the LSTM (2.8e-9 at |logit| 0.004:
# the error follows the hidden sums, which are larger than these logits).
# 32 units leaves room for another machine's codegen and is still ~800 times
# tighter than the nearest thing it must refuse: bfloat16 compute reads
# 25,000 units (test_parity_tolerance_refuses_bf16_compute), a stale or a
# fresh carry and another checkpoint's parameters 25,000 and up (they move
# the first digits).
PARITY_ULPS = 32


def _gap_and_tolerance(got_logits, ref_logits, what):
    got = np.asarray(got_logits, np.float32)
    ref = np.asarray(ref_logits, np.float32)
    assert got.shape == ref.shape, (what, got.shape, ref.shape)
    assert np.isfinite(got).all() and np.isfinite(ref).all(), (what, got, ref)
    scale = max(float(np.abs(got).max()), float(np.abs(ref).max()))
    tol = PARITY_ULPS * float(np.finfo(np.float32).eps) * scale
    return got, ref, float(np.abs(got - ref).max()), tol


def assert_same_answer(got_logits, ref_logits, what="") -> None:
    """Same action, and every logit within ``PARITY_ULPS`` float32 epsilons
    of the largest |logit| in the pair."""
    got, ref, gap, tol = _gap_and_tolerance(got_logits, ref_logits, what)
    assert gap <= tol, (
        f"{what}: logits differ by {gap:.3e}, over the parity tolerance "
        f"{tol:.3e} ({PARITY_ULPS} float32 eps x the largest |logit|): "
        f"{got} vs {ref}")
    assert int(np.argmax(got)) == int(np.argmax(ref)), (what, got, ref)


def assert_other_answer(got_logits, ref_logits, what="") -> None:
    """The logits differ by MORE than the parity tolerance: what each test
    asserts of the answer its guard exists to refuse (a stale or a fresh
    carry, another checkpoint's parameters, bf16 compute), so that the
    tolerance is shown to tell them apart where it is relied on."""
    got, ref, gap, tol = _gap_and_tolerance(got_logits, ref_logits, what)
    assert gap > tol, (
        f"{what}: logits within the parity tolerance ({gap:.3e} <= "
        f"{tol:.3e}) of an answer that should be another: {got} vs {ref}")


class SequentialReference:
    """One-at-a-time ``model.apply`` with carries threaded per session: the
    parity baseline. A session id never stepped before starts from
    ``model.init_carry()``, so a new id fed a request suffix is the cold
    restart the eviction contract names."""

    def __init__(self, model, params):
        self.model = model
        self.params = params
        self._apply = jax.jit(model.apply)
        self._carries: dict = {}

    def step(self, sid, obs):
        carry = self._carries.get(sid)
        if carry is None:
            carry = self.model.init_carry()
        out, carry = self._apply(self.params, obs, carry)
        self._carries[sid] = carry
        logits = np.asarray(out.logits)
        return int(np.argmax(logits)), logits
