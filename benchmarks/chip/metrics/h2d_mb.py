"""Host-device transfer: MB (1e6 bytes) handed to the device per completed
query, padding included (the window's difference of the program's
``device.h2d.bytes.total`` counter)."""
from metrics._phases import h2d_mb as read  # noqa: F401
