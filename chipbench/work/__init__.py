"""Work counts of the kernels and the model, from shapes alone.

Counted per step from the harness's own token counts, never from the
program's tiles, so a count stays right when a later change batches
decode or swaps a kernel.
"""
