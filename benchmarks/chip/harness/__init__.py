"""The chip benchmark's harness: cell lookup, the measured window, the
plain reference that decides ``correct``, and the trace reduction."""
