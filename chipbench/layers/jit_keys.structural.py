"""Kernel: `jit_keys.tenants` in the cell of structural searches:
distinct jit keys the scan program's launches have shown since the
process started: a plan shape is a static of the program, so one key a
template and group shape, whatever values the variants fill in."""
from chipbench.layers.sibling import compute_of

compute = compute_of("jit_keys.tenants")
