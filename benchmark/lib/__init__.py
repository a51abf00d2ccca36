"""The yardstick: window, load generator, trace reduction, work model, peaks."""
