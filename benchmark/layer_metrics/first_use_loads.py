"""Model lifecycle and compile cache: programs the server traced and
loaded (or compiled) INSIDE the measured window, counted from its log
(JAX_LOG_COMPILES lines between the window's edges). On a warm start
the engine loads each dispatch variant on first use; each is a stall of
seconds that the window's rate pays for."""


def reduce(trace, run):
    v = run.get("first_use_loads")
    return None if v is None else float(v)
