"""A configuration module that lacks `summarize`: the harness refuses it."""
from leaf_spine import fabric, generate, program, simulate  # noqa: F401
