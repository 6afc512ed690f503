"""Host milliseconds per lane the dispatcher spends stacking a call's
operands and placing them on the devices: the self time of the program's
`repro.dispatch.stack` and `repro.dispatch.shard` spans in its last
window call, from its in-memory span record; nothing where the program
keeps none."""
import scopes


def read(ctx):
    spans = scopes.last_call_spans()
    if spans is None:
        return None
    return scopes.ms_per_lane(spans, ["repro.dispatch.stack",
                                      "repro.dispatch.shard"])
