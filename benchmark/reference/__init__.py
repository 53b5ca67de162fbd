"""The benchmark's plain reference: a copy of the repo's numpy HTM oracle.

``correct`` is decided against this package and nothing else. It imports
nothing of ``rtap_tpu`` (the program under test) and makes its own initial
state from the seed, so a later PR to the program cannot move the yardstick.
The files are verbatim copies, imports rewritten, of the tree at commit
39be887 (PERF.md lists the originals under Open questions):

    config.py           <- rtap_tpu/config.py
    state.py            <- rtap_tpu/models/state.py  (forward-index branch cut)
    perm.py             <- rtap_tpu/models/perm.py
    hashing.py          <- rtap_tpu/utils/hashing.py
    encoders.py         <- rtap_tpu/models/oracle/encoders.py
    spatial_pooler.py   <- rtap_tpu/models/oracle/spatial_pooler.py
    temporal_memory.py  <- rtap_tpu/models/oracle/temporal_memory.py
    model.py            <- rtap_tpu/models/htm_model.py:oracle_record_step
"""
