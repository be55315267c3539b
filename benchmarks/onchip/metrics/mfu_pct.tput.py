"""Model operations of every token processed in the traced window
(prompt tokens prefilled, tokens generated), over the window times the
chip's peak bf16 FLOP/s."""
from readers import mfu_pct as read  # noqa: F401
