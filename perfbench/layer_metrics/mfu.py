"""Model FLOP/s utilisation: FLOPs a token needs forward and backward
(6 x the parameters outside the embedding lookup + causal attention;
recomputation not counted) x train_tok_s over the bf16 peak."""
from perfbench import roofline

LAYER = 'engine step'
UNIT = '%'
MOVES = 'train_tok_s'
CELLS = ['qwen2.5-1.5b.train']
SOURCE = 'host_clock'


def read(run):
    ctx = run['ctx']
    per_token = roofline.train_flops_per_token(ctx.config['model'],
                                               run['seq'])
    return 100.0 * per_token * run['train_tok_s'] / ctx.peak[
        'bf16_flops_per_s']
