"""Milliseconds per optimizer step of a cell that packs every step: the
whole measured window (host packing inside the benchmark's ``pack`` span,
dispatch, the device barrier and the trainer's bookkeeping) over the steps
completed in it.  Apart from ``train_step_ms`` because the host sets this
pace, and a host's clock spreads ten times wider than a device-bound
step."""

import readers

read = readers.load("train_step_ms").read
