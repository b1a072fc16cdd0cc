"""The signed block cell reads ``correct`` false where the system's block
import verifies only half of each block's signature sets, either half:
its two warm-up twins each carry their one invalid signature in another
half.  The fault is planted where the import verifies a block's batch
(``BlockSignatureVerifier.verify``), on the ``cpp`` backend, which needs
no device compiles; the twins, the window and the reference's check run
as on the chip, at 2^14 real keys and 3-block segments."""
import pytest

from test_signed_blocks import SIGNED, signed_run


def first_half(sets):
    return sets[:len(sets) // 2]


def second_half(sets):
    return sets[len(sets) // 2:]


@pytest.mark.parametrize("half", [None, first_half, second_half])
def test_signed_run_reads_correct_only_with_every_set_verified(
        monkeypatch, half):
    from lighthouse_tpu.state_transition import signature_sets
    if half is not None:
        orig = signature_sets.verify_signature_sets
        monkeypatch.setattr(signature_sets, "verify_signature_sets",
                            lambda sets: orig(half(sets)))
    cpp = {**SIGNED, "config": {**SIGNED["config"], "crypto_backend": "cpp"}}
    result = signed_run(cpp, 2**31 + 13)
    checks = {name: c["value"] for name, c in result["checks"].items()}
    assert checks["reference_verdict_mismatch"] == 0
    if half is None:
        assert result["correct"], checks
        assert result["failed"] == 0
    else:
        assert not result["correct"]
        assert checks["invalid_block_accepted"] == 1, checks
