"""chip_smoke.py's phases at a tiny size on the CPU, and its refusal to
run anywhere but on a TPU."""
import json
import os
import subprocess
import sys

import pytest

import chip_smoke
from lighthouse_tpu.containers import state as st

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def device_hashing():
    """The CPU backend hashes on the host; the smoke asserts the device
    path, so steer the columns onto the XLA kernels as the chip would."""
    old = st._USE_HOST_HASH
    st._USE_HOST_HASH = False
    yield
    st._USE_HOST_HASH = old


def test_import_phase_device_roots_match_host_roots(device_hashing):
    with chip_smoke.Phase("import_1m") as ph:
        out = chip_smoke.phase_import_1m(n_validators=4096, blocks=3)
    rec = ph.record(**out)
    assert rec["device_roots_accepted"] == 3
    assert [b["slot"] for b in rec["imports"]] == [3200002, 3200003,
                                                   3200004]
    assert len({b["state_root"] for b in rec["imports"]}) == 3
    assert rec["use_host_hash"] is False
    assert rec["registry_tree"] == "DeviceTree"
    assert rec["largest_program"]["name"] == "registry DeviceTree build"
    assert rec["largest_program"]["temp_bytes"] > 0
    assert rec["wall_s"] >= rec["compile_s"] >= 0
    json.dumps(rec)


def test_bls_phase_verdicts_agree_with_cpp():
    with chip_smoke.Phase("bls_gossip_10k") as ph:
        out = chip_smoke.phase_bls_gossip_10k(n_sets=64, n_messages=8,
                                              block_sets=6)
    rec = ph.record(**out)
    assert rec["verdicts"] == {"gossip": True, "gossip_corrupted": False,
                               "block": True}
    assert rec["cpp_verdicts"] == rec["verdicts"]
    assert rec["staged_programs"] >= 18
    assert rec["largest_program"]["temp_bytes"] >= 0
    json.dumps(rec)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
