"""The benchmark's own library: discovery, seeds, work counts, peaks,
trace reduction and the result line.  Nothing here imports the program
(``repro``); the drivers under ``bench/drivers`` do."""
