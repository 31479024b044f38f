"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, in %; the same reading
as ``idle_pct.ptycho``, for the tomography cell."""
from chipbench import spec

read = spec.metric_reader("idle_pct.ptycho")
