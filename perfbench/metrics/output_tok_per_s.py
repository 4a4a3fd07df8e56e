"""Every output token that reached the host inside the window, over the window's seconds."""

from perfbench.readings import output_tok_per_s as read  # noqa: F401
