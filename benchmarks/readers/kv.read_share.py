"""`kv.read_share.capacity`: what an in-place read of the paged pool
would touch of what the gather formulation builds, %: `kv_blocks_live`
over `kv_blocks_dense`, summed over the traced stretch's decode
dispatches (args of `sched.decode.dispatch`; program_counter)."""
from benchmarks.decode_dispatch import dispatch_args


def read(ctx, metric):
    got = dispatch_args(
        ctx, "kv_blocks_live", "kv_blocks_dense")
    if got is None or not got["kv_blocks_dense"]:
        return None
    return 100.0 * got["kv_blocks_live"] / got["kv_blocks_dense"]
