"""NEXmark Q11 (user sessions) as the benchmark runs it, at test size on
the CPU, where tier-1 sees it: the cases of
``benchmarks/tests/test_q11_cell.py`` (the generator's constants, the
plain reference on a hand-made stream with ``ts - last == gap``, the
configuration, the bytes model, and the rehearsed cell through
``run_cell`` from the REAL ``benchmarks/`` directory against
``q11_reference.py``, sound and wrong in three ways)."""

from benchmarks.tests import test_q11_cell as _cases

globals().update({name: getattr(_cases, name) for name in dir(_cases)
                  if name.startswith("test_q11_")
                  or name in ("spec", "sound")})
