"""A run of each cell's path at a small size on the CPU reads ``correct``
true, and false with each fault of ``faults.py`` planted underneath.

The harness's look for a chip is skipped; everything else is the run's
own path: generation, warm-up, the window and the comparison.  The
gossip cell runs the device backend at the CPU's 64 lanes; its stage
programs compile once per process, in a few minutes.
"""
import pytest

import faults
import run
from harness import spec

BLOCKS = {"config": {"validators": 1 << 14},
          "traffic": {"segment_blocks": 4}}
GOSSIP = {"config": {"pool_keys": 256},
          "traffic": {"sets_per_batch": 64, "ring": 2, "host_threads": 4}}


def small_run(workload: str, overrides: dict, seed: int,
              seconds: int) -> dict:
    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds)])
    return run.run_cell(args, spec.load(run.ROOT), overrides,
                        require_chip=False)


def block_run(seed=2**31 + 5):
    return small_run("mainnet_1m.block_stream", BLOCKS, seed, 2)


def gossip_run(seed=2**31 + 6):
    # long enough for both batches of the ring (~17 s each on the CPU): the
    # invalid set of the second lies in the other half from the warm-up's
    return small_run("mainnet_1m_all_subnets.gossip_attestations", GOSSIP,
                     seed, 25)


def test_block_stream_sound_run_is_correct():
    result = block_run()
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert result["metrics"]["block_import_ms"]["value"] > 0


@pytest.mark.parametrize("fault", [faults.sync_rewards_skipped,
                                   faults.state_unchanged,
                                   faults.half_attestations,
                                   faults.root_altered])
def test_block_stream_fault_reads_incorrect(monkeypatch, fault):
    fault(monkeypatch)
    assert not block_run()["correct"]


@pytest.mark.parametrize("fault", [None, faults.pairing_skipped,
                                   faults.half_batch, faults.second_half_batch,
                                   faults.verdict_flipped])
def test_gossip_run_reads_correct_only_without_a_fault(monkeypatch, fault):
    if fault is not None:
        fault(monkeypatch)
    result = gossip_run()
    assert result["attempted"] >= 2
    if fault is None:
        assert result["correct"], result["checks"]
        assert result["metrics"]["gossip_sets_per_s"]["value"] > 0
    else:
        assert not result["correct"]
        assert result["checks"]["verdict_mismatch"]["value"] + \
            result["checks"]["warmup_verdict_mismatch"]["value"] > 0
