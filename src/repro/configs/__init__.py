"""The paper's own workload configs (``snap_v_snn``: the SNAP-V MNIST
spiking MLPs and the Cerebra-H accelerator geometry)."""
