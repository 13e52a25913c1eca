"""The benchmark's shared code: catalog (finding a cell's files by name),
fixtures, statistics, the trace reduction and the last line. Nothing
here belongs to one configuration, traffic mix or metric; those sit in
files of their own beside this package."""
