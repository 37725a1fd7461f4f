"""``kernels.drspmm_roofline``, read in the stream cell, where it moves
``stream_step_ms``."""

import readers

read = readers.load("kernels.drspmm_roofline").read
