"""The benchmark's yardstick: traffic generation, order statistics, the
reduction of a profiler trace, the table of peaks and the arithmetic from
shapes to FLOPs and bytes.  Nothing here imports the program under test."""
