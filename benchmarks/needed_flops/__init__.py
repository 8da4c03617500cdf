"""The operations a REQUEST needs, one file a plane (`<plane>.py`, found
by `manifest.needed_flops`).  Part of the yardstick: a function of the
configuration's file, the traffic mix's file and the client's record of
the request, and of nothing the program says or does."""
