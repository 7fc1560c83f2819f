"""`scope.unnamed_share.train` / `.capacity`: of the device's operation
time in the traced stretch (first chip, envelopes left out), the share
whose `tf_op` the scope grammar cannot place (`unnamed`: no scope
reached the instruction, e.g. a copy the compiler inserted) or that was
fused from differently placed origins (`mixed`), %: the gauge of the
grammar itself, as `idle.unattributed_share.*` is of the host spans.
The earlier lines give the whole table and what the unnamed time is
made of, by instruction stem, `hlo_category` and `tf_op`
(`device_scopes.scope_view`) (device_trace).  None on a tree without
the grammar."""
from benchmarks import device_scopes as ds


def read(ctx, metric):
    view = ds.scope_view(ctx)
    if view is None:
        return None
    rows = view[0]
    return ds.share(ds.total(rows, kind=(ds.UNNAMED, "mixed")),
                    ds.total(rows))
