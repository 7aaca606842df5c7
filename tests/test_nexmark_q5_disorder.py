"""NEXmark Q5 over an out-of-order bid stream as the benchmark runs it, at
test size on the CPU, where tier-1 sees it: the cases of
``benchmarks/tests/test_q5_disorder_cell.py`` (the lag function that
defines the disorder, the arrival-order reference against a per-record
fold, the configuration beside its control's, and the rehearsed cell
through ``run_cell`` from the REAL ``benchmarks/`` directory against
``q5_disorder_reference.py``, sound, with no holdback, and wrong in three
ways)."""

from benchmarks.tests import test_q5_disorder_cell as _cases

globals().update({name: getattr(_cases, name) for name in dir(_cases)
                  if name.startswith("test_q5_disorder_")
                  or name in ("spec", "sound")})
