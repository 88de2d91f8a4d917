"""1 - union of device-op intervals / traced window (mean over chips)."""
LAYER = 'device'
UNIT = '%'
MOVES = 'train_tok_s'
CELLS = ['qwen2.5-1.5b.train']
SOURCE = 'device_trace'


def read(run):
    tr = run['trace']
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
