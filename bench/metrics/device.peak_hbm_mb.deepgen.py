"""``device.peak_hbm_mb``, read in the DeepGEN cell."""

import readers

read = readers.load("device.peak_hbm_mb").read
