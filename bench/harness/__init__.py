"""The benchmark's yardstick: spec loading, seeds, the peak table, FLOP and
byte counts, the trace reduction, the comparisons that decide ``correct``,
and one driver per kind of traffic. Nothing here is imported by the program
under test; the drivers import the program."""
