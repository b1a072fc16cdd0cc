"""The ``tpu`` BLS backend with its real kernels on the CPU, chosen and
compiled as a node's start-up does it (``ClientBuilder.build``:
``bls.set_backend`` then ``precompile``).  Each verdict must equal the
C++ reference's, and no program that start-up compiled may compile again
inside a verify."""
import random

import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import SignatureSet
from lighthouse_tpu.crypto.bls.cpp_backend import CppBackend
from lighthouse_tpu.crypto.bls12_381 import R

N_SETS = 64
N_MESSAGES = 8
BLOCK_SETS = 6

#: (program name) of every backend compile while a listener is on
_compiled: list[str] | None = None


def _on_duration(event: str, duration: float, fun_name="?", **kw):
    if (_compiled is not None
            and event == "/jax/core/compile/backend_compile_duration"):
        _compiled.append(fun_name)


@pytest.fixture(scope="module")
def cpp():
    return CppBackend()


@pytest.fixture(scope="module")
def batches(cpp):
    """The gossip batch (64 single-key sets over 8 messages), its twin
    with one set in the second half carrying another set's valid
    signature, and a block-sized batch of 6 distinct messages."""
    rng = random.Random(27)
    sks = [rng.randrange(1, R) for _ in range(N_SETS)]
    messages = [rng.randbytes(32) for _ in range(N_MESSAGES)]

    def signed(sk, msg):
        return SignatureSet(cpp.sign(sk, msg), [cpp.sk_to_pk(sk)], msg)

    gossip = [signed(sk, messages[i % N_MESSAGES])
              for i, sk in enumerate(sks)]
    bad = list(gossip)
    i = N_SETS // 2 + 3
    bad[i] = SignatureSet(gossip[i + 1].signature, gossip[i].pubkeys,
                          gossip[i].message)
    block = [signed(sk, rng.randbytes(32)) for sk in sks[:BLOCK_SETS]]
    return {"gossip": gossip, "gossip_corrupted": bad, "block": block}


@pytest.fixture(scope="module")
def tpu_backend():
    """The ``tpu`` backend as start-up leaves it: selected, then every
    stage program compiled; yields (backend, names of those programs)."""
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bls, "_current", None)
        tpu = bls.set_backend("tpu")
        staged = tpu.precompile()
        yield tpu, {f"jit({name})" for name, _ in staged}


@pytest.mark.parametrize("batch,valid", [("gossip", True),
                                         ("gossip_corrupted", False),
                                         ("block", True)])
def test_precompiled_tpu_verdict_matches_cpp(tpu_backend, batches, cpp,
                                             batch, valid):
    global _compiled
    tpu, staged = tpu_backend
    sets = batches[batch]
    _compiled = []
    try:
        verdict = tpu.verify_signature_sets(sets)
        compiled_again = staged.intersection(_compiled)
    finally:
        _compiled = None
    assert verdict == valid
    assert cpp.verify_signature_sets(sets) == valid
    assert not compiled_again
