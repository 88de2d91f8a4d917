"""1 - union of device-op intervals / traced window (mean over chips)."""
LAYER = 'device'
UNIT = '%'
MOVES = 'tpot_p95_ms'
CELLS = ['jamba2-3b.docreason']
SOURCE = 'device_trace'


def read(run):
    tr = run['trace']
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
