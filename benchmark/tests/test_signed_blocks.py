"""The signed block cell's path at a small size on the CPU: 2^14 real
keys, 3-block segments with every signature real.  On the ``tpu``
backend (the CPU's 64 lanes; its stage programs compile once per
process, in ~10 minutes) the run reads ``correct`` true with every check
at 0; on the ``fake`` backend, which accepts any signature, both invalid
twins are imported and the run reads false.  The byte count of the HBM
share follows the traffic's shares."""
import run
from harness import signed_gen, spec

SIGNED = {"config": {"validators": 1 << 14},
          "traffic": {"segment_blocks": 3, "settle_imports": 1,
                      "host_threads": 4}}
CHECKS = {"anchor_root_mismatch", "blocks_refused", "last_root_mismatch",
          "reference_verdict_mismatch", "invalid_block_accepted"}


def signed_run(overrides: dict, seed: int, trace: int = 0) -> dict:
    args = run.parse(["--workload", "mainnet_1m_signed.signed_block_stream",
                      "--seed", str(seed), "--seconds", "2", "--trace",
                      str(trace)])
    return run.run_cell(args, spec.load(run.ROOT), overrides,
                        require_chip=False)


def test_signed_blocks_on_the_device_backend_read_correct():
    result = signed_run(SIGNED, 2**31 + 11, trace=1)
    assert set(result["checks"]) == CHECKS
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    # span readers find the batch, its host stages and the pubkey sums;
    # a CPU trace has no device plane, so the device readers say nothing
    for name in ("block_bls.verify_ms", "block_bls.host_ms",
                 "block_bls.pk_aggregate_ms"):
        assert result["metrics"][name]["value"] > 0, name
    assert "block_bls.pk_aggregate_hbm_share" not in result["metrics"]


def test_fake_backend_control_imports_the_invalid_twin():
    control = {**SIGNED, "config": {**SIGNED["config"],
                                    "crypto_backend": "fake"}}
    result = signed_run(control, 2**31 + 12)
    assert result["checks"]["invalid_block_accepted"]["value"] == 2
    assert result["checks"]["reference_verdict_mismatch"]["value"] == 0
    assert not result["correct"]


def test_aggregated_bytes_follow_the_traffic():
    bench = spec.load(run.ROOT)
    cfg = spec.config(bench, run.ROOT, "mainnet_1m_signed")
    traffic = spec.traffic("signed_block_stream")
    # the proposal, the randao reveal, 64 aggregates of 512 keys and a
    # 512-key sync aggregate: 33,282 keys read (256 bytes each), 67 sums
    # written (384 bytes each)
    assert signed_gen.aggregated_bytes(cfg, traffic) == \
        33282 * 256 + 67 * 384
    half = {**traffic, "attesting_committees": 0.5, "sync_bits": 0.0}
    assert signed_gen.aggregated_bytes(cfg, half) == \
        (2 + 32 * 512) * 256 + 34 * 384
