"""Share of the v5e's HBM bandwidth that the device pubkey sums reach, in
%: 100 x the bytes they must move (each key of a block's sets read from
the device table, each set's sum written;
``harness/signed_gen.aggregated_bytes``, per block) over their device
time x 819 GB/s, in the traced slice.  The device time is that of the
programs ``g1_table_gather`` and ``g1_bucket_sum`` in the trace; each
run of ``g1_bucket_sum`` sums one block's keys (a full block at 2^20
validators is one chunk of ``tpu_backend.key_shape``).  819 GB/s is the
published HBM bandwidth of one TPU v5e chip (the figure
``lighthouse_tpu/obs/roofline.PEAKS`` holds).  None where the trace
holds neither program."""

HBM_BYTES_PER_S = 819e9
PROGRAMS = ("g1_table_gather", "g1_bucket_sum")


def read(ctx):
    if not ctx.trace:
        return None
    seconds = sum(ctx.trace["programs"].get(p, 0.0) for p in PROGRAMS)
    blocks = ctx.trace["runs"].get("g1_bucket_sum")
    per_block = ctx.traced.get("pk_aggregate_bytes")
    if not seconds or not blocks or not per_block:
        return None
    return 100 * per_block * blocks / (seconds * HBM_BYTES_PER_S)
