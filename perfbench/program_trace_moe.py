"""Device time under the scopes that ``program_trace``'s classes do not
tell apart: ``moe_route``, ``moe_expert``, ``moe_shared`` (inside
``mlp``), ``mhc`` (inside a ``layerNN``) and ``mtp`` (a whole
multi-token module).  Every instant of busy time goes to the innermost
operation covering it, as there; a name gets the time of every
operation whose scope path holds it, so ``mtp`` overlaps the others
and these are no partition.  None where the program has no map, the
trace no chip, or nothing ran under the name."""
from __future__ import annotations

from typing import Dict, Optional

from . import program_trace as pt

NAMES = ("moe_route", "moe_expert", "moe_shared", "mhc", "mtp")


def path_ms(ctx: Dict) -> Optional[Dict[str, float]]:
    """``{name: device ms a finished step}`` for ``NAMES``."""
    def make():
        maps = pt.program_maps(ctx)
        if maps is None or ctx["busy"] is None:
            return None
        from mxnet_tpu.traceview import scope_path

        labels: Dict = {}

        def label_of(program, hlo_line):
            key = (program, pt.instruction(hlo_line))
            if key not in labels:
                path = scope_path(maps.get(program, {}).get(key[1], ""))
                labels[key] = ",".join(n for n in NAMES if n in path)
            return labels[key]

        ns = pt.innermost(pt._labelled(ctx, label_of))
        steps = ctx["counters"]["steps"]
        return {name: sum(v for k, v in ns.items()
                          if name in k.split(",")) / 1e6 / steps
                for name in NAMES}
    return pt._once(ctx, "moe_path_ms", make)


def name_ms(ctx: Dict, name: str) -> Optional[float]:
    table = path_ms(ctx)
    if table is None or not table[name]:
        return None
    return table[name]
