"""Mean of the CLIENT's tnet.consume_to_cut over the window in the served
tensor cell: the read that brought a reply's first bytes -> the reply cut;
the copy of the reply out of the link. `tnet_client_cut_1m_mean_us`'s reading
(the same stage of the same table, 1 MiB + 4 B replies over the same link),
under this cell's name: the table is the one benchmark/client/tensor_load.cc
dumps after its warm-up and after its drain; None where the client sent no
table."""
from benchmark import manifest

_echo = manifest.reader("tnet_client_cut_1m_mean_us")
LAYER, UNIT, MOVES, SOURCE = _echo.LAYER, _echo.UNIT, _echo.MOVES, _echo.SOURCE
read = _echo.read
