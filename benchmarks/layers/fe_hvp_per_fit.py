"""Hessian-vector products a fit's fixed-effect TRON solves made, as the
optimizer counted them (`OptResult.hv_evals`, one a CG iteration, refused
steps' included): the window's `hessian_vector_products{kind=fixed}` over the
window's fits. They are among `fe_evals_per_fit`'s passes over the data; the
rest of those are value+gradient evaluations. The window's total is the
process total less the warm fit's (`fit_timing["hv_evals"]`), as
layers/stages.py reads its counts. None where the program counts no product:
another solver, or a commit before the counter."""


def read(run):
    from photon_ml_tpu.utils import telemetry

    counted = telemetry.METRICS.labeled_counters("hessian_vector_products")
    warm = (run["warm_fit_timing"] or {}).get("hv_evals")
    cids = [c for c, kind in run["kinds"].items() if kind == "fixed"]
    keys = [f"coordinate={c},kind=fixed" for c in cids]
    if warm is None or not run["records"] or not any(k in counted for k in keys):
        return None
    total = sum(counted.get(k, 0) for k in keys) - sum(warm.get(c, 0) for c in cids)
    return total / len(run["records"])
