"""The plain reference of the light client's SEQUENTIAL verification
(reference: light/client.go:608 verifySequential): the plain
``verify_adjacent`` (``light/reference.py``; light/verifier.go:91) of every
header from trusted + 1 to the target, in height order, stopping at the
first that is not accepted.

A loop over the one-step reference and nothing else: no scheduler, no
signature cache, no batching, no device, no jax.  The tests
(``tests/test_light_sequential.py``) hold ``LightClient(mode=SEQUENTIAL)``
and ``light/verifier.verify_adjacent_chain`` to it;
``benchmarks/light_seq_ref.py`` is a copy that differs in the one import
line below.

``verify_sequential`` returns ``(verdict, height)`` of the first header that
is not accepted, ``verdict`` as ``verify_adjacent`` gives it, or
``("accepted",)``.

Departures from light/client.go, each on purpose:

  * verdicts are tuples, not ``ErrVerificationFailed{From, To, Reason}``:
    the height is ``To``, the verdict ``Reason``'s class;
  * no primary is replaced: on an invalid INTERMEDIATE header Go asks a
    witness for a new primary and tries that height again
    (``findNewPrimary``); with no witness, as here, it returns the error;
  * the headers are given, not fetched from a provider;
  * the divergence check against witnesses (``detectDivergence``) and the
    store write after it belong to the client, not to this loop.
"""

from __future__ import annotations

from cometbft_tpu.light.reference import _ed, verify_adjacent


def verify_sequential(chain_id: str, trusted, news, trusting_period_s: float,
                      now_s: float, verify_sig=_ed.verify_zip215) -> tuple:
    """light/client.go:608: ``news`` are the headers trusted + 1, trusted +
    2, ..., each checked against the one before it."""
    current = trusted
    for new in news:
        got = verify_adjacent(chain_id, current, new, trusting_period_s, now_s,
                              verify_sig=verify_sig)
        if got != ("accepted",):
            return got, new.header.height
        current = new
    return ("accepted",)
