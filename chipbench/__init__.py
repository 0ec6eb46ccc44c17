"""On-chip benchmark of the repository's train, reduce and serve paths.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the chips of
the machine it starts on and prints one JSON result line. Everything that
belongs to one configuration, traffic mix, cell limit or per-layer metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json     the configuration as run (source, reduced,
                            assumed, departures)
  traffic/<mix>.json        parameters for the one generator in traffic.py;
                            its ``driver`` names the path driver
  limits/<cell>.json        the limits of the numbers that decide
                            ``correct``, with the readings they came from
  metrics/<metric>.py       one reader per per-layer metric
  drivers/<driver>.py       set-up, window and comparison of one path
"""
