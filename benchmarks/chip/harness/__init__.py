"""The chip benchmark's shared code: cell lookup, the timed windows, the
trace reduction, operation counts and the table of peaks."""
