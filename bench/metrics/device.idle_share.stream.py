"""``device.idle_share``, read in the stream cell, where it moves
``stream_step_ms``."""

import readers

read = readers.load("device.idle_share").read
