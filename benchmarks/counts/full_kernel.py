"""ONE call of the ragged paged attention kernel by a FULL layer of a
SmallThinker step (every key of a row's context, no window):
``counts/window_kernel.py`` has the arithmetic for both kinds."""
from .window_kernel import count_full as count  # noqa: F401
