"""Drivers: what runs the system under test for one kind of configuration.

A configuration file may name its driver, ``"driver": "<name>"``; without
the key it is ``gossip_linear``. The harness loads
``chipbench/drivers/<name>.py`` by that name, as it finds configurations,
traffic mixes and metric readers, so a configuration with a new driver is
new files and new BENCHMARK.json entries, and no edit of the harness.

A driver module has a class ``Driver(cell, seed)`` with

  program         the name of the timed program in a trace (``XLA Modules``
                  events), whose executions the readers count;
  open_at         the operation whose hook opens the window (the driver's
                  compared operations run before it, in set-up);
  samples_per_op  samples one operation completes (``samples_per_s``);
  info            what metric readers get besides the trace: ``m``, ``n``,
                  ``chunk_rounds`` (steps of one operation), and anything of
                  its own;
  prepare()       set-up before the system runs (the traffic);
  keep(*args)     called by the window's hook at its open, with the hook's
                  arguments: keeps what the readings compare;
  run(hook)       drives the system, calling ``hook(*args)`` after every
                  operation, until it returns True;
  readings(ops)   once the window has closed and the memory peak is read:
                  frees the system's state, runs the plain reference and
                  returns {name: number} for the configuration's ``limits``
                  (``ops`` is how many operations ran);
  release()       drops the traffic.
"""
