"""Mean of the server's stage tdev.take_wait over the window exactly: the C++
handler parked the Put -> the taker thread's `tpurpc_server_take` got it. A
Put waits here while the one taker cuts the calls ahead of it into chunks; it
lies inside trpc.handler. `tensor_take_wait_mean_us`'s reading of the same
stage, under this layer's name."""
from benchmark import manifest

_tensor = manifest.reader("tensor_take_wait_mean_us")
LAYER = "served cache hand-off (brpc_tpu/kv_service.py + DeviceLane + c_api pull server)"
UNIT, MOVES, SOURCE = _tensor.UNIT, _tensor.MOVES, _tensor.SOURCE
read = _tensor.read
