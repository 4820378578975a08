"""Traffic generators: each generates one kind of traffic from a mix's
parameters and runs it against the program (see ``chipbench.harness``)."""
