"""How much of a Put's residence in the server is the device leg: the mean of
the server's stage trpc.handler (handler entered -> `done->Run()`: the wait to
be taken, every chunk's copy into a slot, frame, H2D and step, and the wait
for the last chunk's word) over the mean of the five stages' sum
(benchmark.stages.RESIDENCE), both over the window exactly, in %:
`tensor_device_leg_share`'s reading of the same stages, under this layer's
name. Over 50 means the leg, not the link, sets the pace of a cache
hand-off."""
from benchmark import manifest

_tensor = manifest.reader("tensor_device_leg_share")
LAYER = "served cache hand-off (brpc_tpu/kv_service.py + DeviceLane + c_api pull server)"
UNIT, MOVES, SOURCE = _tensor.UNIT, _tensor.MOVES, _tensor.SOURCE
read = _tensor.read
