"""The yardstick of the chip benchmark: data and load generation, the
plain reference, trace reduction, roofline arithmetic and the harness.

Nothing here is imported by the system under test, and nothing here
imports it except ``harness`` and the drivers, which build and drive it.
"""
