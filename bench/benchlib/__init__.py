"""The benchmark's yardstick: inputs, references, work counts, peaks and
the reduction from traces and spans to metrics.  Nothing here is imported
by the program under test."""
