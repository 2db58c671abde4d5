"""compile_s.radius: seconds JAX spent compiling (tracing, lowering,
backend compile or cache load) inside the traced window of a cell that
reports radius_qps: the union of the program's `obs.compile` marks
(spans.compile_seconds)."""

from spans import compile_seconds as read  # noqa: F401
