"""1 - (union of device-op intervals over the traced window), on the one chip
whose memory the cache hand-off fills: `device_idle_share.ring`'s reading,
under this cell's name."""
from benchmark import manifest

_ring = manifest.reader("device_idle_share.ring")
LAYER, UNIT, MOVES, SOURCE = _ring.LAYER, _ring.UNIT, _ring.MOVES, _ring.SOURCE
read = _ring.read
