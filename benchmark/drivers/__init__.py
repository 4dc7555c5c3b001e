"""One module per kind of run: `run(run, devices)` sets the cell up, drives
the timed window and compares what it produced with the plain reference."""
