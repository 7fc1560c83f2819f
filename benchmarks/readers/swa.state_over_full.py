"""`swa.state_over_full.capacity`: the bytes of key/value state the
live sequences hold, over what they would hold with every layer a full
one, %: the blocks their tables point at (`kv_blocks_live` of a
dispatch: every live row's pages up to its position) times the FULL
layers' block bytes, plus every slot's rings (`swa_rows_read` rows),
over those blocks' tokens times a row of every layer; the mean over
the traced stretch's dispatches of either program (span args and the
family's `latent_block_bytes`, `swa_read_bytes`, `kv_row_bytes`;
program_counter).  None for a family without those, and where the
spans carry no such args."""
from benchmarks import host_spans as hs


def read(ctx, metric):
    fam, cfg = ctx.family, ctx.cfg
    if not all(hasattr(fam, f) for f in (
            "latent_block_bytes", "swa_read_bytes", "kv_row_bytes")):
        return None
    found = hs.ring(ctx)
    if found is None:
        return None
    spans = [r for r in found[0]
             if r.name in ("sched.decode.dispatch", "sched.prefill.dispatch")
             and "kv_blocks_live" in r.args and "swa_rows_read" in r.args
             and r.args["kv_blocks_live"]]
    if not spans:
        return None
    page = cfg["deployment"]["kv_page_size"]
    blocks = sum(r.args["kv_blocks_live"] for r in spans) / len(spans)
    rings = fam.swa_read_bytes(
        cfg, sum(r.args["swa_rows_read"] for r in spans) / len(spans))
    held = blocks * fam.latent_block_bytes(cfg) + rings
    every = blocks * page * cfg["num_hidden_layers"] * fam.kv_row_bytes(cfg)
    ctx.out(f"swa.state_over_full: {blocks:.0f} live blocks a dispatch hold "
            f"{held / 1e9:.3f} GB ({rings / 1e9:.3f} of rings) against "
            f"{every / 1e9:.3f} GB with every layer a full one")
    return 100.0 * held / every
