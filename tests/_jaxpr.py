"""Jaxpr inspection shared by the kernel and train-step tests."""


def count_kernel_calls(jaxpr, kernel: str) -> int:
    """``pallas_call``s of the kernel function named ``kernel`` in a
    jaxpr, sub-jaxprs (scan, remat, shard_map, custom rules) included."""
    n = 0
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["jaxpr"].debug_info.func_name == kernel):
            n += 1
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += count_kernel_calls(sub, kernel)
    return n
