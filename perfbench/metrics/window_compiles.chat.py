"""Compiles (persistent-cache reads included) inside the measured window."""

from perfbench.readings import window_compiles as read  # noqa: F401
