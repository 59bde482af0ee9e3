"""Mean rows served per engine step in the window (the `rows` argument of
`engine.step`): at a fixed offered rate, fewer rows in flight means
requests leave sooner."""
from program_spans import rows_per_step as read  # noqa: F401
