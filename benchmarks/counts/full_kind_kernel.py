"""ONE call of the ragged paged attention kernel by a FULL layer of a
configuration whose head count goes by the kind of layer (every key of
a row's context, 48 query heads where the window layers have 64):
``counts/window_kind_kernel.py`` has the arithmetic for both kinds."""
from .window_kind_kernel import count_full as count  # noqa: F401
