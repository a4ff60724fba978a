"""The chip benchmark's yardstick: cell discovery, device checks, seeded
traffic, trace reduction and the comparison that decides ``correct``.

Nothing here is imported by the program under test; the cell runners import the
program (``src/repro``) only to build and drive the system being measured.
"""
