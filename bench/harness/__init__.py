"""The benchmark's harness: cells, device, weights, serving window,
trace reduction, kernel costs and the float32 reference."""
