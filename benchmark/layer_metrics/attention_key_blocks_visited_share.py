"""Step program: of the (query block, key block) tiles of every attention
layer's square, the share the kernel's grids were launched with:
``attention_key_blocks_visited / attention_key_blocks_square`` of the
``train_epoch`` spans (each epoch's last drained values), in percent;
median over the window's epochs. A causal mask alone leaves a little over
half, a window less; 100 is a kernel that masks and does not skip. Nothing
where the program counts no such blocks."""

from benchmark.layer_metrics._routed import window_epochs_attr
from benchmark.stats import median


def read(obs):
    visited = window_epochs_attr(obs, "attention_key_blocks_visited")
    square = window_epochs_attr(obs, "attention_key_blocks_square")
    if not visited or not square or not all(square):
        return None
    return median([100.0 * v / s for v, s in zip(visited, square)])
