"""Mean of the CLIENT's tici.link_handoff over the window in the served
tensor cell: the server posts a reply's link descriptor -> the client's pump
consumes it. `tici_reply_handoff_1m_mean_us`'s reading (the same stage of the
same table, 1 MiB + 4 B replies over the same link), under this cell's name:
the table is the one benchmark/client/tensor_load.cc dumps after its warm-up
and after its drain; None where the client sent no table."""
from benchmark import manifest

_echo = manifest.reader("tici_reply_handoff_1m_mean_us")
LAYER, UNIT, MOVES, SOURCE = _echo.LAYER, _echo.UNIT, _echo.MOVES, _echo.SOURCE
read = _echo.read
