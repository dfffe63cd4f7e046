"""Device milliseconds per execution of one compiled program under the
named scopes (``benchlib/scopes.py``: ``attn``, ``attn/flash_fwd``,
``optimizer``, ``unscoped``, ...), forward and backward together, from the
traced stretch.  A scope the program never entered reads 0; a run whose
trace was not reduced by scope, or a program that records no scope map,
has nothing to read."""


def read(run, program, scopes):
    prog = ((run["trace"] or {}).get("scopes") or {}).get(program)
    if not prog or not prog["count"]:
        return None
    seconds = sum(
        sum(prog["scopes"].get(scope, {}).values()) for scope in scopes
    )
    return 1e3 * seconds / prog["count"]
