"""`decode.in_pass_share.capacity`: of the rows that took a sampled
token in the traced stretch, the share that took it inside a
chunked-prefill pass, %: the `decode_rows` arg of
`sched.prefill.dispatch` (rows sampled from that dispatch's logits:
the step plan in which a row past its prompt rides the pass, ISSUE 52)
over that plus the `rows` arg of `sched.decode.dispatch` (rows past
their prompt in a decode dispatch), summed over the stretch
(program_counter).  0.0 where no span carries `decode_rows` (a program
whose pass returns no logits: the parent of PR 52, or the scan); None
where the stretch dispatched neither program or sampled no row."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    found = hs.ring(ctx)
    if found is None:
        return None
    passes = hs.named(found[0], "sched.prefill.dispatch")
    steps = hs.named(found[0], "sched.decode.dispatch")
    in_pass = sum(r.args.get("decode_rows", 0) for r in passes)
    in_step = sum(r.args["rows"] for r in steps)
    if not in_pass + in_step:
        return None
    ctx.out(f"decode.in_pass_share: {in_pass} rows sampled from "
            f"{len(passes)} prefill dispatches, {in_step} from "
            f"{len(steps)} decode dispatches")
    return 100.0 * in_pass / (in_pass + in_step)
