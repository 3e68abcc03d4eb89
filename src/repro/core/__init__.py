"""repro.core — the CUTHERMO reproduction: TPU memory heat-map profiling.

Levels:
  1. ``collector`` — BlockSpec/grid walker (static-exact HBM<->VMEM map)
  2. ``collector.drain_dynamic`` — in-kernel trace buffers for gathers
  3. ``hlo_thermo`` — distributed (compiled-HLO) heat + collective bytes

The public API lives in ``repro.core.api``.  This package imports none
of its submodules, so importing one (``repro.core.spec`` from a kernel
module, say) loads only what that submodule needs.
"""
