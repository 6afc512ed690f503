"""MiB of device state one lane holds: the `lane_bytes` count of the
program's `repro.exec.plan` span in its last window call (the planner's
`per_lane_bytes`, from which it sets the chunk width), from its in-memory
span record; nothing where the program keeps no record or the span
carries no such count."""
import scopes


def read(ctx):
    spans = scopes.last_call_spans()
    if spans is None:
        return None
    sizes = [s.stats["lane_bytes"] for s in spans
             if s.name == "repro.exec.plan" and "lane_bytes" in s.stats]
    if not sizes:
        return None
    return max(sizes) / 2 ** 20
