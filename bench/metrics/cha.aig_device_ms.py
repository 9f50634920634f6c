"""Device time of the cone-simulation programs (`kernels/aig_sim`: the
Pallas evaluator, the jnp mega-program and the signature program) per
transform application finished in the traced window."""

#: Name fragments of the aig_sim programs in the trace.
PROGRAMS = ("eval_batch", "eval_mega", "sig_eval")


def read(m):
    red = m["trace"]
    apps = m["counters"].get("applications", 0)
    if not apps or not red.devices:
        return None
    per_dev = [sum(v for k, v in progs.items() if any(p in k for p in PROGRAMS))
               for progs in red.programs.values()]
    return max(per_dev) / apps * 1e3
