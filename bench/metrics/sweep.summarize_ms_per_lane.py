"""Host milliseconds per lane the sweep spends turning each case's state
and emits into its run metrics: the self time of the program's
`repro.sweep.summarize` spans in its last window call, from its in-memory
span record; nothing where the program keeps none."""
import scopes


def read(ctx):
    spans = scopes.last_call_spans()
    if spans is None:
        return None
    return scopes.ms_per_lane(spans, ["repro.sweep.summarize"])
