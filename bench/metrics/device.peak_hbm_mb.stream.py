"""``device.peak_hbm_mb``, read in the stream cell, where it moves
``stream_step_ms``."""

import readers

read = readers.load("device.peak_hbm_mb").read
