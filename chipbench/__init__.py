"""Chip benchmark of the compressed train step.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the chips of
the machine it is started on. Everything that belongs to one model
configuration, one traffic mix, one cell or one per-layer metric is a file
of its own under this directory, found by the name ``BENCHMARK.json``
gives it:

- ``configs/<config>.json``: the sizes as run, the registry arch and the
  fields replaced in it, the source and every cut, the weight init rules;
- ``traffic/<traffic>.json``: batch, sequence, optimizer and every
  compression setting of the job;
- ``workloads/<cell>.json``: the mesh and the limits of the correctness
  comparison, with the readings each limit was set from;
- ``metrics/<metric>.py``: one reader per per-layer metric;
- ``families/<family>.py``: one architecture's layout, sizes, FLOPs and
  reference layer, found by the ``family`` a configuration names.

The yardstick lives here too: the weights and token batches made from the
seed (``weights.py``, ``data.py``), the plain float32 reference of the
step (``reference.py``) and the comparison with it (``compare.py``), the
reduction of a profiler trace (``trace.py``), model FLOPs (``flops.py``)
and the table of peaks (``peaks.json``).
"""
