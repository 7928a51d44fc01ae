"""The dense Hessian-vector kernel's events in a trace, and a product's least time.

`ops/pallas_glm.hessian_vector_sums` names its Pallas call, so the device
trace holds one `hessian_vector_sums[.n]` event a product: a truncated CG
iteration of a TRON solve (optimize/tron.py). The fixed effect's
value+gradient evaluations stay `kernels.fixed_effect_kernel`'s to count.
"""

import re

from .. import work, work_hvp

KERNEL = re.compile(r"^hessian_vector_sums(\.\d+)?$")


def traced_products(run):
    """(products, their device seconds, least seconds a product) in the
    traced fits, or None without a trace, a dense fixed effect or such an
    event (a program that falls back to XLA's two matmuls has none)."""
    trace = run["trace"]
    product = work_hvp.fixed_effect_product(run["config"], run["rows"])
    if not trace or product is None:
        return None
    names = [n for n in trace["op_self_s"] if KERNEL.match(n)]
    calls = sum(trace["op_count"][n] for n in names)
    seconds = sum(trace["op_self_s"][n] for n in names)
    if not calls or seconds <= 0.0:
        return None
    return calls, seconds, work.least_seconds(product, run["peaks"])[0]
