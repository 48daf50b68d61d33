"""Compiles (backend compiles and persistent-cache loads) between the
window's start and the last answer."""

from bench.readers import window_compiles as read  # noqa: F401
