"""``device.idle_share``, read in the DeepGEN cell."""

import readers

read = readers.load("device.idle_share").read
