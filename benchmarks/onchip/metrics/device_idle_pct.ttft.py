"""Share of the traced window in which no operation ran on the device
while the engine held queued or resident work."""
from readers import device_idle_pct as read  # noqa: F401
