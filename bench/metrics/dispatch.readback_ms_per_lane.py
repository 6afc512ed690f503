"""Host milliseconds per lane the dispatcher spends copying a call's
results to the host and trimming their padded lanes: the self time of the
program's `repro.dispatch.readback` spans in its last window call, from
its in-memory span record; nothing where the program keeps none."""
import scopes


def read(ctx):
    spans = scopes.last_call_spans()
    if spans is None:
        return None
    return scopes.ms_per_lane(spans, ["repro.dispatch.readback"])
